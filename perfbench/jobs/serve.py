"""The serving job: the LLM engine under an open loop of requests.

The system under test is ``serve.llm.LLMEngine`` with its own loop thread.
The harness wraps three of its public calls to time them and to put
``pb.*`` spans on the profiler's clock: ``engine.step`` (one iteration of
the loop: a prefill or a decode) and the model runner's ``prefill`` and
``decode``.  One generator thread submits each request when it is due; one
collector thread polls the streams and stamps each token as a client would
see it.  A thread per request would take the interpreter lock from the
engine's host-bound loop and lengthen the very step being measured.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from perfbench import manifest, stats, trace, traffic

# Prefill and decode run with bf16 activations (8 bits of mantissa) and the
# reference is float32, so equal mathematics agrees to some hundredths, more
# with more layers: the tolerance is the configuration's own
# (``serve.logit_atol`` in its file, with the reason beside it).
#
# A family that routes (``families/<f>.routed(config)`` is not None).  Where
# the float32 reference scores two experts a rounding apart the program
# decides either way, both are correct executions, and one expert taken the
# other way moves single logits by more than any tolerance that still
# catches a fault.  So the program says what it chose: its runner has
# ``route_spec`` ({"layers", "k"}, as ``state_spec`` describes recurrent
# state) and, after each ``prefill`` / ``decode``, ``choices``: the chosen
# expert ids of that step, int (routed layers, rows of the bucket, k), from
# the run that made the logits.  The reference is computed UNDER that choice
# and audits it in its own scores; the two limits of the audit are the
# configuration's too, and a routed configuration without them is refused.
POLL_S = 0.002
ROUTE_LIMITS = ("route_margin", "route_differing_share")


class _Rec:
    """One request as the client sees it."""
    __slots__ = ("due", "sent", "max_tokens", "stream", "times", "done",
                 "error")

    def __init__(self, due, max_tokens):
        self.due, self.max_tokens = due, max_tokens
        self.sent = None
        self.stream = None
        self.times = []
        self.done = False
        self.error = None


class Served:
    """The engine, instrumented, with the log of its steps."""

    def __init__(self, ctx: dict):
        import jax

        from ray_tpu.serve import llm
        from ray_tpu.serve.llm.config import resolve_model

        self.config, self.spec = ctx["config_file"], ctx["traffic_file"]
        self.fam = manifest.family(self.config["family"])
        kwargs = dict(self.config["serve"]["engine"])
        for key in ("decode_batch_buckets", "prefill_len_buckets"):
            kwargs[key] = tuple(kwargs[key])
        self.ecfg = llm.EngineConfig(seed=traffic.key_seed(ctx["seed"]),
                                     **kwargs)
        mod, mcfg = resolve_model(self.ecfg)
        self.fam.check_sizes(self.config, mcfg)
        describe = getattr(self.fam, "routed", None)
        self.routed = describe(self.config) if describe else None
        for key in ROUTE_LIMITS if self.routed else ():
            if key not in self.config["serve"] \
                    or f"why_{key}" not in self.config["serve"]:
                raise ValueError(
                    f"the configuration routes ({self.routed}) and its file "
                    f"has no serve.{key} with a why_{key} beside it: the "
                    "serving check holds a routed model to limits measured "
                    "for it, never to a default (perfbench/README.md)")
        # the weights in one jitted call on the device, in the type served
        self.params = jax.jit(lambda key: mod.init_params(key, mcfg))(
            jax.random.key(self.ecfg.seed))
        jax.block_until_ready(self.params["wte"])
        marks, t_start = ctx["marks"], ctx["t_start"]
        marks["weights_s"] = time.perf_counter() - t_start
        self.llm = llm
        self.eng = llm.LLMEngine(self.ecfg, params=self.params, start=False)
        marks["engine_s"] = time.perf_counter() - t_start
        offered = getattr(self.eng.runner, "route_spec", None)
        if self.routed and (not offered or any(
                offered[key] != self.routed[key] for key in ("layers", "k"))):
            self.eng.shutdown()
            raise RuntimeError(
                f"the configuration routes ({self.routed}) and the program's "
                f"runner offers {offered!r} as its route_spec: without the "
                "experts the program chose there is no reference for its "
                "logits (perfbench/README.md, a routed family)")
        self.steps = []         # (t0, t1, kind, running, waiting, preempted)
        self._instrument()
        self._warm_programs()
        marks["programs_s"] = time.perf_counter() - t_start
        self.eng.start()

    def _instrument(self) -> None:
        eng, log = self.eng, self.steps
        step, runner = eng.step, eng.runner
        prefill, decode = runner.prefill, runner.decode

        def timed_step():
            before = eng.stats()
            t0 = time.perf_counter()
            with trace.span("pb.step"):
                ran = step()
            t1 = time.perf_counter()
            after = eng.stats()
            kind = ("prefill" if after["prefill_steps"] > before["prefill_steps"]
                    else "decode" if after["decode_steps"] > before["decode_steps"]
                    else "none")
            log.append((t0, t1, kind, before["running"], before["waiting"],
                        after["preemptions"]))
            return ran

        def spanned(name, fn):
            def call(*args, **kwargs):
                with trace.span(name):
                    return fn(*args, **kwargs)
            return call

        eng.step = timed_step
        runner.prefill = spanned("pb.prefill.run", prefill)
        runner.decode = spanned("pb.decode.run", decode)

    def _warm_programs(self) -> None:
        """Every program the traffic will use, once; and every page of the
        KV pool touched, since the whole pool crosses to the device in
        each decode step and its pages exist only after a first write."""
        eng, ecfg = self.eng, self.ecfg
        eng.cache.pool.fill(0)
        buckets = sorted({_bucket(p, ecfg.prefill_len_buckets)
                          for p, _ in traffic.length_grid(self.spec)})
        for b in buckets:
            eng.runner.prefill([0] * b)
        maxb = ecfg.max_blocks_per_seq
        for b in ecfg.decode_batch_buckets:
            if b <= _bucket(ecfg.max_num_seqs, ecfg.decode_batch_buckets):
                eng.runner.decode(
                    np.zeros(b, np.int32), np.zeros(b, np.int32),
                    eng.cache.pool, np.zeros((b, maxb), np.int32),
                    np.ones(b, np.int32))

    # ------------------------------------------------------------ measuring
    def measure(self, ctx: dict, rate_scale: float = 1.0) -> dict:
        """Play the cycle: ``warm_seconds`` of it, then the window."""
        spec, eng = self.spec, self.eng
        seconds, warm_s = ctx["seconds"], spec["warm_seconds"]
        vocab = self.config["vocab_size"]
        cycle = traffic.serve_cycle(spec, vocab, ctx["seed"], rate_scale)
        period = traffic.cycle_seconds(spec, rate_scale)
        plan = traffic.schedule(cycle, period, warm_s, seconds)
        recs = [_Rec(r.due_s, r.max_tokens) for r in plan]
        stop = threading.Event()
        t_open = time.perf_counter() + warm_s + 0.05     # time 0 of the plan
        active, inbox = [], []

        def generate():
            for req, rec in zip(plan, recs):
                wait = t_open + req.due_s - time.perf_counter()
                if wait > 0 and stop.wait(wait):
                    return
                if stop.is_set():
                    return
                with trace.span("pb.submit"):
                    try:
                        rec.stream = eng.submit(
                            list(req.prompt),
                            self.llm.SamplingParams(max_tokens=req.max_tokens))
                    except Exception as e:  # noqa: BLE001 - a refusal counts
                        rec.error = repr(e)
                rec.sent = time.perf_counter()
                if rec.stream is not None:
                    inbox.append(rec)

        def collect():
            while not stop.is_set():
                while inbox:
                    active.append(inbox.pop(0))
                for rec in list(active):
                    try:
                        toks, done = rec.stream.poll(max_items=64, timeout=0)
                    except RuntimeError as e:
                        rec.error, done, toks = repr(e), True, []
                    if toks:
                        now = time.perf_counter()
                        rec.times.extend([now] * len(toks))
                    if done:
                        rec.done = True
                        active.remove(rec)
                time.sleep(POLL_S)

        threads = [threading.Thread(target=generate, name="pb-generator"),
                   threading.Thread(target=collect, name="pb-collector")]
        for t in threads:
            t.start()
        capture = None
        if ctx["trace"]:
            capture = trace.Capture(ctx["trace_dir"])
            lead = seconds - min(spec["trace_seconds"], seconds)
            time.sleep(max(0.0, t_open + lead - time.perf_counter()))
            capture.start()
        time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
        t_close = time.perf_counter()
        traced = None
        if capture:
            capture.stop()
        stop.set()
        for t in threads:
            t.join()
        for rec in recs:
            if rec.stream is not None and not rec.done:
                rec.stream.cancel()
        self._wait_idle()
        if capture:
            traced = trace.rename_by_child(
                trace.load_window(capture), "pb.step",
                {"pb.decode.run": "pb.decode", "pb.prefill.run": "pb.prefill"},
                "pb.step_idle")
        facts = self._reduce(recs, t_open, t_close, ctx.get("notes", False))
        facts["trace"] = traced
        return facts

    def _wait_idle(self, limit_s: float = 20.0) -> None:
        deadline = time.perf_counter() + limit_s
        while time.perf_counter() < deadline:
            s = self.eng.stats()
            if s["running"] == 0 and s["waiting"] == 0:
                return
            time.sleep(0.05)
        raise RuntimeError("the engine did not go idle after the window")

    def _reduce(self, recs, t_open: float, t_close: float,
                with_notes: bool) -> dict:
        spec = self.spec
        window_s = t_close - t_open

        def inside(t):
            return t_open <= t <= t_close

        itl, ttft, late, out_tokens = [], [], [], 0
        attempted = failed = wrong_length = 0
        for rec in recs:
            out_tokens += sum(1 for t in rec.times if inside(t))
            itl += [(b - a) * 1e3 for a, b in zip(rec.times, rec.times[1:])
                    if inside(b)]
            if rec.done and rec.error is None and \
                    len(rec.times) != rec.max_tokens:
                wrong_length += 1
            if not 0.0 <= rec.due < window_s:
                continue
            attempted += 1
            due = t_open + rec.due
            if rec.sent is not None:
                late.append((rec.sent - due) * 1e3)
            if rec.times:
                ttft.append((rec.times[0] - due) * 1e3)
            # due to be finished inside the window under the cell's limits
            limit = due + spec["ttft_limit_s"] \
                + rec.max_tokens * spec["itl_limit_s"]
            if rec.error is not None or (limit <= t_close and not rec.done):
                failed += 1
        log = [s for s in self.steps if inside(s[1])]
        decode = [s for s in log if s[2] == "decode"]
        prefill = [s for s in log if s[2] == "prefill"]
        before = [s for s in self.steps if s[1] < t_open]
        return {
            "t_open": t_open, "window_s": window_s, "out_tokens": out_tokens,
            "itl_ms": itl, "ttft_ms": ttft, "late_ms": late,
            "decode_step_ms": [(s[1] - s[0]) * 1e3 for s in decode],
            "prefill_step_ms": [(s[1] - s[0]) * 1e3 for s in prefill],
            "occupancy_pct": [100.0 * s[3] / self.ecfg.max_num_seqs
                              for s in decode],
            "preemptions": (log[-1][5] if log else 0)
            - (before[-1][5] if before else 0),
            "attempted": attempted, "failed": failed,
            "wrong_length": wrong_length,
            "notes": self._notes(itl, log, decode, t_open, window_s,
                                 attempted) if with_notes else {},
        }

    def _notes(self, itl, log, decode, t_open, window_s, attempted) -> dict:
        """(builder) what the sweep and the histogram check read."""
        thirds = [[(s[1] - s[0]) * 1e3 for s in decode
                   if i / 3 <= (s[1] - t_open) / window_s < (i + 1) / 3]
                  for i in range(3)]

        def waiting(lo, hi):
            return [s[4] for s in log
                    if lo <= (s[1] - t_open) / window_s < hi]

        bins: dict = {}
        for g in itl:
            key = int(g // 10) * 10
            bins[key] = bins.get(key, 0) + 1
        return {
            "requests_due": attempted, "itl_gaps": len(itl),
            "offered_rps": traffic.rate_rps(self.spec),
            "itl_percentiles_ms": {
                str(q): stats.percentile(itl, q)
                for q in (50, 75, 80, 85, 90, 95, 99)} if itl else {},
            "decode_step_ms_by_third": [
                stats.percentile(t, 50) if t else None for t in thirds],
            "waiting_mid": _mean(waiting(0.4, 0.5)),
            "waiting_end": _mean(waiting(0.9, 1.0)),
            "itl_histogram_10ms": dict(sorted(bins.items())),
        }

    # -------------------------------------------------------- correctness
    def _choices(self, rows: int) -> np.ndarray:
        """The expert ids the step just run chose, for its first ``rows``
        rows: (routed layers, rows, k), as the runner holds them."""
        want = self.routed
        ids = np.asarray(self.eng.runner.choices)
        if ids.ndim != 3 or ids.shape[0] != want["layers"] \
                or ids.shape[1] < rows or ids.shape[2] != want["k"] \
                or ids.dtype.kind not in "iu":
            raise ValueError(f"the runner's choices are {ids.dtype}"
                             f"{list(ids.shape)}: not {rows} rows or more of "
                             f"{want}")
        ids = ids[:, :rows].astype(np.int32)
        if ids.min() < 0 or ids.max() >= want["experts"]:
            raise ValueError("the runner's choices name an expert outside "
                             f"0..{want['experts'] - 1}")
        return ids

    def check_logits(self, seed: int) -> dict:
        """Outside the window: one prompt through prefill, then decode
        steps through the paged cache as the engine's loop makes them,
        against the plain reference's full forward; for a family that
        routes, the reference under the experts those steps chose, and the
        choice against the reference's own scores."""
        eng, spec = self.eng, self.spec
        runner, cache = eng.runner, eng.cache
        n, k = spec["check_prompt_tokens"], spec["check_decode_steps"]
        prompt = [int(t) for t in traffic.rng_for(seed, "serve_check")
                  .integers(0, self.config["vocab_size"], n)]
        sid = "pb_check"
        cache.alloc_seq(sid, n)
        try:
            logits, ks, vs = runner.prefill(prompt)
            chose = [self._choices(n)] if self.routed else []
            cache.scatter_prefill(sid, np.asarray(ks, np.float32),
                                  np.asarray(vs, np.float32), n)
            got, seq = [logits], list(prompt)
            maxb = self.ecfg.max_blocks_per_seq
            for _ in range(k):
                seq.append(int(np.argmax(got[-1])))
                blk, off, _ = cache.append_slot(sid)
                tables = np.zeros((1, maxb), np.int32)
                table = cache.table(sid)
                tables[0, :len(table)] = table
                at = np.asarray([len(seq) - 1], np.int32)
                lg, ks, vs = runner.decode(
                    np.asarray([seq[-1]], np.int32), at, cache.pool,
                    tables, at)
                if self.routed:
                    chose.append(self._choices(1))
                cache.write_token(blk, off, np.asarray(ks[:, 0], np.float32),
                                  np.asarray(vs[:, 0], np.float32))
                got.append(lg[0])
        finally:
            cache.free_seq(sid)
        limits, audit = self.config["serve"], None
        if self.routed:
            # the ids of every position of seq, (routed layers, n + k, K)
            ref, audit = self.fam.reference_logits(
                self.params, [seq], self.config,
                choices=np.concatenate(chose, axis=1))
        else:
            ref = self.fam.reference_logits(self.params, [seq], self.config)
        ref = np.asarray(ref)[0]
        diffs = [float(np.abs(g - ref[n - 1 + i]).max())
                 for i, g in enumerate(got)]
        out = {"prefill_logit_diff": diffs[0],
               "decode_logit_diff": max(diffs[1:]),
               "logit_atol": limits["logit_atol"]}
        if audit:
            out.update(
                route_decisions=audit["decisions"],
                route_differing=audit["differing"],
                route_worst_margin=audit["worst_margin"],
                route_margin=limits["route_margin"],
                route_differing_share=limits["route_differing_share"])
        out["ok"] = all(value <= limit
                        for value, limit in _compared(out).values())
        return out

    def close(self) -> None:
        self.eng.shutdown()


def _bucket(n: int, buckets) -> int:
    return next(b for b in sorted(buckets) if n <= b)


def _mean(xs):
    return stats.mean(xs) if xs else None


def _compared(check: dict) -> dict:
    """Each number of ``check_logits`` that decides its verdict, beside its
    limit: name -> [value, limit]."""
    atol = check["logit_atol"]
    out = {"prefill_logit_diff": [check["prefill_logit_diff"], atol],
           "decode_logit_diff": [check["decode_logit_diff"], atol]}
    if "route_decisions" in check:
        out["route_worst_margin"] = [check["route_worst_margin"],
                                     check["route_margin"]]
        out["route_differing"] = [
            check["route_differing"],
            check["route_differing_share"] * check["route_decisions"]]
    return out


def run(ctx: dict) -> dict:
    served = Served(ctx)
    try:
        facts = served.measure(ctx)
        check = served.check_logits(ctx["seed"])
    finally:
        served.close()
    checks = {"every_request_has_max_tokens": facts["wrong_length"] == 0,
              "logits_vs_reference": check["ok"]}
    # the warm-up traffic is set-up: the window opens when it has brought
    # the sequence slots to their steady occupancy
    facts["setup_s"] = facts.pop("t_open") - ctx["t_start"]
    facts["correct"] = all(checks.values())
    facts["checks"] = checks
    facts["compared"] = {"wrong_length": [facts["wrong_length"], 0],
                         **_compared(check)}
    facts["notes"].update(check)
    return facts
