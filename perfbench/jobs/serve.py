"""The serving job: the LLM engine under an open loop of requests.

The system under test is ``serve.llm.LLMEngine`` with its own loop thread.
The harness wraps three of its public calls to time them and to put
``pb.*`` spans on the profiler's clock: ``engine.step`` (one iteration of
the loop: a prefill or a decode) and the model runner's ``prefill`` and
``decode``.  One generator thread submits each request when it is due; one
collector thread polls the streams and stamps each token as a client would
see it.  A thread per request would take the interpreter lock from the
engine's host-bound loop and lengthen the very step being measured.

The collector looks at the streams when an iteration of the loop has ended
(``Served.stepped``, set by the wrapper round ``engine.step``) and every
``POLL_S`` besides.  On a sleep of ``POLL_S`` alone a stamp falls on a tick
of ~2.15 ms, and a step of 2.5 ticks reads as two or three, half and half:
the median of all gaps then lies in the empty valley between two modes and
moves by 1-3% from run to run on steps that repeat to 0.5% (PERF.md section
7).  The stamp is the collector's own clock when it finds the token.
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
#
# A family that does not step by tokens (``families/<f>.stepping(config)`` is
# not None) says how its sequences are stepped: an object with ``warm(served)``
# and ``check(served, prompt, k)`` in the place of ``TokenStepping`` below.
# What is compared, with what and under which limits stays here: ``check``
# hands back a list of ``Compared``, each one plain forward's worth,
#   {"fed": [ids], "rows": [(phase, position, logits (V,)), ...],
#    "choices": ids (routed layers, len(fed), k), for a family that routes}
# ``fed`` every id one pass of the program saw at positions 0..len-1 (a mask
# id where it saw one), ``rows`` the positions of ``fed`` whose logits that
# pass produced, each under "prefill" or "decode" (perfbench/README.md).
POLL_S = 0.002
ROUTE_LIMITS = ("route_margin", "route_differing_share")
PHASES = ("prefill", "decode")


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
        describe = getattr(self.fam, "stepping", None)
        self.stepping = (describe(self.config) if describe else None) \
            or TokenStepping()
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
        self.stepped = threading.Event()    # an iteration ended: look now
        self._instrument()
        self.stepping.warm(self)
        marks["programs_s"] = time.perf_counter() - t_start
        self.eng.start()

    def _instrument(self) -> None:
        eng, log, stepped = self.eng, self.steps, self.stepped
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
            stepped.set()
            return ran

        def spanned(name, fn):
            def call(*args, **kwargs):
                with trace.span(name):
                    return fn(*args, **kwargs)
            return call

        eng.step = timed_step
        runner.prefill = spanned("pb.prefill.run", prefill)
        runner.decode = spanned("pb.decode.run", decode)

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
        stop, stepped = threading.Event(), self.stepped
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
                stepped.wait(POLL_S)
                stepped.clear()

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
    def _expert_ids(self, ids, rows: int, whose: str,
                    or_more: bool = False) -> np.ndarray:
        """``ids`` as int32 (routed layers, ``rows``, k), each an expert the
        configuration has; refused, in ``whose`` name, where they are not."""
        want = self.routed
        ids = np.asarray(ids)
        if ids.ndim != 3 or ids.shape[0] != want["layers"] \
                or ids.shape[2] != want["k"] or ids.dtype.kind not in "iu" \
                or not (rows <= ids.shape[1] if or_more
                        else rows == ids.shape[1]):
            raise ValueError(f"{whose} choices are {ids.dtype}"
                             f"{list(ids.shape)}: not {rows} rows"
                             f"{' or more' if or_more else ''} of {want}")
        ids = ids[:, :rows].astype(np.int32)
        if ids.min() < 0 or ids.max() >= want["experts"]:
            raise ValueError(f"{whose} choices name an expert outside "
                             f"0..{want['experts'] - 1}")
        return ids

    def _choices(self, rows: int) -> np.ndarray:
        """The expert ids the step just run chose, for its first ``rows``
        rows: (routed layers, rows, k), as the runner holds them."""
        return self._expert_ids(self.eng.runner.choices, rows,
                                "the runner's", or_more=True)

    def _sound(self, one: dict):
        """(fed, rows, choices) of one ``Compared``, refused by name where
        it is not what the header says."""
        if set(one) - {"fed", "rows", "choices"}:
            raise ValueError(f"a Compared has the keys {sorted(one)}: not "
                             "fed, rows and, for a routed family, choices")
        fed = [int(t) for t in one["fed"]]
        vocab = self.config["vocab_size"]
        if not fed or min(fed) < 0 or max(fed) >= vocab:
            raise ValueError(f"a Compared's fed holds {len(fed)} ids, which "
                             f"have to be one or more of 0..{vocab - 1}")
        rows = [(phase, int(at), np.asarray(logits, np.float32))
                for phase, at, logits in one["rows"]]
        for phase, at, _ in rows:
            if phase not in PHASES or not 0 <= at < len(fed):
                raise ValueError(
                    f"a Compared's row ({phase!r}, {at}, ...) is not one of "
                    f"{PHASES} at a position of its {len(fed)} fed ids")
        if not rows:
            raise ValueError("a Compared has no rows: nothing of its pass "
                             "is compared")
        has = one.get("choices") is not None
        if bool(self.routed) != has:
            raise ValueError(
                f"the configuration routes ({self.routed}) and a Compared "
                f"{'has' if has else 'has no'} choices")
        # an expert of every routed layer's k at every position of fed, or
        # the reference has nothing to go under
        return fed, rows, (self._expert_ids(one["choices"], len(fed),
                                            "a Compared's")
                           if self.routed else None)

    def check_logits(self, seed: int) -> dict:
        """Outside the window: one prompt stepped as the family says (the
        job's own ``TokenStepping``: prefill, then decode steps through the
        paged cache as the engine's loop makes them), and every pass of it
        against the plain reference's full forward over what that pass was
        fed; for a family that routes, the reference under the experts the
        pass chose, and the choice against the reference's own scores."""
        spec = self.spec
        n, k = spec["check_prompt_tokens"], spec["check_decode_steps"]
        prompt = [int(t) for t in traffic.rng_for(seed, "serve_check")
                  .integers(0, self.config["vocab_size"], n)]
        return self._judge(self.stepping.check(self, prompt, k))

    def _judge(self, compared: list) -> dict:
        """Each ``Compared`` against one reference forward over its ``fed``:
        the largest difference over the rows of each phase, the audits of a
        routed family merged, under the configuration's limits."""
        limits, diffs, audits = self.config["serve"], {}, []
        for one in compared:
            fed, rows, choices = self._sound(one)
            if self.routed:
                ref, audit = self.fam.reference_logits(
                    self.params, [fed], self.config, choices=choices)
                audits.append(audit)
            else:
                ref = self.fam.reference_logits(self.params, [fed],
                                                self.config)
            ref = np.asarray(ref)[0]
            for phase, at, logits in rows:
                if logits.shape != ref[at].shape:
                    raise ValueError(
                        f"a Compared's {phase} row at {at} has logits "
                        f"{list(logits.shape)} and the reference's are "
                        f"{list(ref[at].shape)}")
                diffs.setdefault(phase, []).append(
                    float(np.abs(logits - ref[at]).max()))
        for phase in PHASES:
            if phase not in diffs:
                raise ValueError(f"the family's check compared no {phase} "
                                 "row: both phases decide `correct`")
        # numpy's max and not Python's: a NaN among the rows stays a NaN,
        # which is under no limit
        out = {"prefill_logit_diff": float(np.max(diffs["prefill"])),
               "decode_logit_diff": float(np.max(diffs["decode"])),
               "logit_atol": limits["logit_atol"]}
        if audits:
            out.update(
                route_decisions=sum(a["decisions"] for a in audits),
                route_differing=sum(a["differing"] for a in audits),
                route_worst_margin=max(a["worst_margin"] for a in audits),
                route_margin=limits["route_margin"],
                route_differing_share=limits["route_differing_share"])
        out["ok"] = all(value <= limit
                        for value, limit in _compared(out).values())
        return out

    def close(self) -> None:
        self.eng.shutdown()


class TokenStepping:
    """The job's own stepping, for a family that says nothing of its own
    (``stepping`` absent or None): a step is one new position a row, whose
    K/V the step that computes it writes."""

    def warm(self, served: Served) -> None:
        """Every program the traffic will use, once; and every page of the
        KV pool touched, since the whole pool crosses to the device in
        each decode step and its pages exist only after a first write."""
        eng, ecfg = served.eng, served.ecfg
        eng.cache.pool.fill(0)
        buckets = sorted({_bucket(p, ecfg.prefill_len_buckets)
                          for p, _ in traffic.length_grid(served.spec)})
        for b in buckets:
            eng.runner.prefill([0] * b)
        maxb = ecfg.max_blocks_per_seq
        for b in ecfg.decode_batch_buckets:
            if b <= _bucket(ecfg.max_num_seqs, ecfg.decode_batch_buckets):
                eng.runner.decode(
                    np.zeros(b, np.int32), np.zeros(b, np.int32),
                    eng.cache.pool, np.zeros((b, maxb), np.int32),
                    np.ones(b, np.int32))

    def check(self, served: Served, prompt: list, k: int) -> list:
        """The prompt through prefill, then ``k`` greedy decode steps of one
        token: ONE ``Compared`` over the final sequence, the prefill's last
        position and each step's."""
        runner, cache = served.eng.runner, served.eng.cache
        n = len(prompt)
        sid = "pb_check"
        cache.alloc_seq(sid, n)
        try:
            logits, ks, vs = runner.prefill(prompt)
            chose = [served._choices(n)] if served.routed else []
            cache.scatter_prefill(sid, np.asarray(ks, np.float32),
                                  np.asarray(vs, np.float32), n)
            got, seq = [logits], list(prompt)
            maxb = served.ecfg.max_blocks_per_seq
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
                if served.routed:
                    chose.append(served._choices(1))
                cache.write_token(blk, off, np.asarray(ks[:, 0], np.float32),
                                  np.asarray(vs[:, 0], np.float32))
                got.append(lg[0])
        finally:
            cache.free_seq(sid)
        one = {"fed": seq,
               "rows": [("prefill" if i == 0 else "decode", n - 1 + i, g)
                        for i, g in enumerate(got)]}
        if served.routed:
            # the ids of every position of seq, (routed layers, n + k, K)
            one["choices"] = np.concatenate(chose, axis=1)
        return [one]


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
