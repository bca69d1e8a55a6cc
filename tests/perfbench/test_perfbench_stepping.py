"""How a sequence is stepped is the family's to say (``families/<f>.py``'s
``stepping(config_file)``); what is compared, with what and under which
limits stays the serving job's (``perfbench/jobs/serve.py``;
``perfbench/README.md``, "A family that does not step by tokens").

No program of this tree steps by anything but tokens, so the contract is
held here by the families that exist, through its default (``gpt2:tiny`` and
``lfm2:tiny`` against the parent's ``check_logits``, kept below as the
oracle), and by a stub family that is admitted as the next ``model_config``
PR will have to admit a real one: an adapter module found by
``manifest.family`` (through ``sys.modules``), a configuration and a traffic
file under ``tmp_path``, and no edit to a file under ``perfbench/``.  The
stub drives the toy's one-token runner and reports it as a family whose pass
covers a block would: two positions a ``Compared``, one ``Compared`` a
block.  The toys are causal, so a forward over the sequence so far agrees
with one over the whole at the positions it has.

The stub's limits were set as PERF.md sets a cell's, from readings on the
CPU (24-token prompt, 3 blocks; seeds 0-11): ``gpt2:tiny`` reads logit
differences of at most 4.0e-3 (three times that is the limit), a row under
its neighbour's position 0.233 or more, a row whose own id is changed in
``fed`` 0.68 or more; ``lfm2:tiny`` has the files and the limits of
``test_perfbench_lfm2.py``.
"""

import json
import sys
import time
import types

import numpy as np
import pytest

import test_perfbench_lfm2 as lfm2_toy
from perfbench import manifest, traffic
from perfbench.families import gpt2, lfm2

STUB = "blockstub"
CELL = "blockstub.serve-blocks"
PROMPT, BLOCKS = 24, 3
LIMITS = {"logit_atol": 0.012,
          "why_logit_atol": "three times 4.0e-3; a neighbour 0.233"}


# ------------------------------------------------------------ the parent
def parent_check_logits(served, seed: int) -> dict:
    """``Served.check_logits`` as PR 62 had it, letter for letter but for
    ``self`` -> ``served``: the oracle of the default."""
    from perfbench.jobs.serve import _compared
    self = served
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


# --------------------------------------------------- the toys' own files
def _overrides() -> dict:
    return json.loads((manifest.BENCH_DIR / "rehearsal" / "overrides.json")
                      .read_text())


def _toy_files(base: str, family: str):
    """(configuration, traffic) as files would hold them, for ``gpt2:tiny``
    or ``lfm2:tiny`` under the family named."""
    over = _overrides()
    if base == "gpt2":
        config = {"family": family, **over["config"],
                  "serve": {"engine": dict(over["serve_engine"]),
                            **LIMITS}}
    else:
        config = {**lfm2_toy._tiny_ctx(0)["config_file"], "family": family}
    spec = json.loads((manifest.BENCH_DIR / "traffic" /
                       "serve-chat-busy.json").read_text())
    spec = {**spec, **over["traffic"]["serve"],
            "check_prompt_tokens": PROMPT, "check_decode_steps": BLOCKS}
    return config, spec


def _ctx(config: dict, spec: dict, seed: int) -> dict:
    """The job's context as run.prepare builds it."""
    return {"config_file": config, "traffic_file": spec, "seed": seed,
            "seconds": 0.5, "trace": False, "notes": True, "marks": {},
            "t_start": time.perf_counter()}


# ------------------------------------------------------ (a) the default
@pytest.mark.parametrize("seed", [5, 2 ** 31 + 9, 77])
@pytest.mark.parametrize("base", ["gpt2", "lfm2"])
def test_the_default_reads_what_the_parent_read(base, seed):
    """Neither family exports ``stepping``: the job's own ``TokenStepping``
    makes ONE ``Compared`` over the final sequence, and every number of the
    check is the parent's to the last bit."""
    from perfbench.jobs import serve
    assert not hasattr(manifest.family(base), "stepping")
    served = serve.Served(_ctx(*_toy_files(base, base), seed))
    try:
        assert isinstance(served.stepping, serve.TokenStepping)
        new = served.check_logits(seed)
        old = parent_check_logits(served, seed)
    finally:
        served.close()
    assert new == old and new["ok"]
    assert serve._compared(new) == serve._compared(old)
    assert ("route_decisions" in new) == (base == "lfm2")


# ------------------------------------------------- the stub, added as files
class PairStepping:
    """Two positions a ``Compared``, ``k`` of them: the prompt's last
    position and ``2 k - 1`` greedy steps of the toy's runner, driven by the
    job's own stepping and cut into blocks.  ``fault`` plants one departure
    where the check is reported."""

    def __init__(self, fault=None):
        self.fault, self.warmed = fault, []

    def warm(self, served) -> None:
        # before the loop's thread exists; the toy compiles where it runs
        self.warmed.append(served.eng._thread is None)
        served.eng.cache.pool.fill(0)
        served.eng.runner.prefill([0] * 16)

    def check(self, served, prompt, k) -> list:
        from perfbench.jobs import serve
        (whole,) = serve.TokenStepping().check(served, prompt, 2 * k - 1)
        out = []
        for block in range(k):
            rows = whole["rows"][2 * block:2 * block + 2]
            one = {"fed": whole["fed"][:rows[-1][1] + 1], "rows": rows}
            if served.routed:
                one["choices"] = whole["choices"][:, :len(one["fed"])]
            out.append(one)
        if self.fault == "neighbour":       # a row under the position before
            phase, at, lg = out[1]["rows"][1]
            out[1]["rows"][1] = (phase, at - 1, lg)
        elif self.fault == "fed":           # one id is not what the pass saw
            fed = list(out[2]["fed"])
            fed[-1] = (fed[-1] + 1) % served.config["vocab_size"]
            out[2]["fed"] = fed
        elif self.fault == "choices":       # one position short
            out[0]["choices"] = out[0]["choices"][:, :-1]
        elif self.fault == "phase":
            out[0]["rows"][0] = ("denoise",) + out[0]["rows"][0][1:]
        elif self.fault == "beyond":        # a position fed does not have
            phase, at, lg = out[0]["rows"][1]
            out[0]["rows"][1] = (phase, len(out[0]["fed"]), lg)
        elif self.fault == "no_prefill":
            out[0]["rows"] = out[0]["rows"][1:]
        elif self.fault == "unasked_choices":
            out[0]["choices"] = np.zeros((1, len(out[0]["fed"]), 1),
                                         np.int32)
        return out


@pytest.fixture
def stub(monkeypatch, tmp_path):
    """``admit(base, fault) -> (cell, stepping, audits)``: the adapter module
    where ``manifest.family`` finds it, the configuration and the traffic
    as files under ``tmp_path``, the cell loaded from them BY NAME."""
    def admit(base: str, fault=None):
        basefam = {"gpt2": gpt2, "lfm2": lfm2}[base]
        stepping, audits = PairStepping(fault), []
        mod = types.ModuleType(f"perfbench.families.{STUB}")
        mod.check_sizes = basefam.check_sizes
        mod.routed = getattr(basefam, "routed", lambda config_file: None)

        def reference_logits(params, tokens, config_file, **kwargs):
            out = basefam.reference_logits(params, tokens, config_file,
                                           **kwargs)
            audits.append(out[1] if kwargs else None)
            return out

        def shrunk(config_file):
            return base != "gpt2" and lfm2.shrunk(config_file)

        mod.reference_logits = reference_logits
        mod.stepping = lambda config_file: \
            None if shrunk(config_file) else stepping
        monkeypatch.setitem(sys.modules, mod.__name__, mod)
        config, spec = _toy_files(base, STUB)
        files = {f"perfbench/configs/{STUB}.json": config,
                 "perfbench/traffic/serve-blocks.json": spec}
        for name, body in files.items():
            (tmp_path / name).parent.mkdir(parents=True, exist_ok=True)
            (tmp_path / name).write_text(json.dumps(body))
        bench = {"configs": [{"name": STUB,
                              "file": f"perfbench/configs/{STUB}.json"}],
                 "workloads": [{"name": CELL, "config": STUB,
                                "traffic": "serve-blocks", "chips": 1}]}
        cell = manifest.load_cell(bench, CELL, root=tmp_path)
        assert manifest.family(cell["config_file"]["family"]) is mod
        return cell, stepping, audits
    return admit


def _cell_ctx(cell: dict, seed: int) -> dict:
    return _ctx(cell["config_file"], cell["traffic_file"], seed)


# -------------------------------- (b), (d) the stub through the whole job
@pytest.mark.parametrize("base", ["gpt2", "lfm2"])
def test_a_family_that_reports_blocks_goes_through_the_job(stub, base,
                                                           monkeypatch):
    """Served(ctx) -> the window -> the stub's check: three ``Compared``,
    three reference calls, the routed audits merged by sum and max, and
    `correct` under the names every cell has.  Its ``warm`` ran once,
    before the loop's thread, and the job's own did not."""
    from perfbench.jobs import serve
    cell, stepping, audits = stub(base)
    own = []
    monkeypatch.setattr(serve.TokenStepping, "warm",
                        lambda self, served: own.append(served))
    facts = serve.run(_cell_ctx(cell, seed=2 ** 31 + 3))
    assert stepping.warmed == [True] and not own
    assert facts["correct"] and facts["checks"]["logits_vs_reference"]
    assert facts["attempted"] > 0 and facts["wrong_length"] == 0
    assert len(audits) == BLOCKS
    notes, compared = facts["notes"], facts["compared"]
    assert 0 < notes["prefill_logit_diff"] < notes["logit_atol"]
    assert 0 < notes["decode_logit_diff"] < notes["logit_atol"]
    if base == "gpt2":
        assert audits == [None] * BLOCKS
        assert set(compared) == {"wrong_length", "prefill_logit_diff",
                                 "decode_logit_diff"}
        return
    layers = lfm2.routed(cell["config_file"])["layers"]
    assert [a["decisions"] for a in audits] == [
        layers * (PROMPT + 2 * b + 1) for b in range(BLOCKS)]
    assert notes["route_decisions"] == sum(a["decisions"] for a in audits)
    assert notes["route_differing"] == sum(a["differing"] for a in audits)
    assert notes["route_worst_margin"] == max(a["worst_margin"]
                                              for a in audits)
    assert compared["route_differing"] == [
        notes["route_differing"],
        notes["route_differing_share"] * notes["route_decisions"]]
    assert compared["route_worst_margin"][0] <= notes["route_margin"]


# --------------------------- (c) what the job fails, and what it refuses
def _checked(cell, seed=4):
    from perfbench.jobs import serve
    served = serve.Served(_cell_ctx(cell, seed))
    try:
        return served.check_logits(seed)
    finally:
        served.close()


@pytest.mark.parametrize("fault, name", [
    ("neighbour", "decode_logit_diff"), ("fed", "decode_logit_diff")])
def test_a_wrong_report_fails_by_the_number_it_moves(stub, fault, name):
    """A row under its neighbour's position, or a ``fed`` that is not what
    the pass saw: the reference differs, and `correct` is false by name."""
    from perfbench.jobs import serve
    cell, _, _ = stub("gpt2", fault)
    check = _checked(cell)
    assert not check["ok"], check
    value, limit = serve._compared(check)[name]
    assert value > 3 * limit
    assert check["prefill_logit_diff"] <= limit     # block 0 is sound


@pytest.mark.parametrize("base, fault, said", [
    ("lfm2", "choices", r"choices are int32\[8, 24, 2\]"),
    ("gpt2", "unasked_choices", "has choices"),
    ("gpt2", "phase", "'denoise'"),
    ("gpt2", "beyond", r"\('decode', 25, "),
    ("gpt2", "no_prefill", "no prefill row")])
def test_a_report_the_job_cannot_compare_is_refused(stub, base, fault,
                                                    said):
    cell, _, _ = stub(base, fault)
    with pytest.raises(ValueError, match=said):
        _checked(cell)


# --------------------------------------- (e) a rehearsal gets the default
def test_a_shrunk_configuration_of_such_a_family_gets_the_default(stub):
    """What --rehearse makes of the stub's cell: the toy GPT-2 under the
    family's name, stepped and checked by the job's own."""
    from perfbench import run
    from perfbench.jobs import serve
    cell, stepping, audits = stub("lfm2")
    shrunk = run._rehearsal_cell(cell)
    assert lfm2.shrunk(shrunk["config_file"])
    served = serve.Served(_cell_ctx(shrunk, seed=6))
    try:
        assert isinstance(served.stepping, serve.TokenStepping)
        assert served.routed is None and not stepping.warmed
        check = served.check_logits(6)
    finally:
        served.close()
    assert check["ok"] and audits == [None]
    assert check == {k: check[k] for k in (
        "prefill_logit_diff", "decode_logit_diff", "logit_atol", "ok")}
