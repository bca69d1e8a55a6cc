"""The serving check on a family that routes: ``llama:tiny-moe`` (OLMoE's
block: 8 experts, 2 a token) through ``Served(ctx)`` -> ``check_logits`` in
its serving type, bfloat16, against ``reference/olmoe_ref.py`` under the
experts the program chose.

The program's hand-over of its choice is laid over it by
``routed_handover.install`` (this tree's program has none; see there).  The
weights are the program's ``init_params`` brought to a trained model's scale
(unit-variance streams, experts that carry the residual): at 0.02 a token's
experts add a hundredth of its residual and nothing a router does shows.

The limits below were set as PERF.md sets a cell's, from readings on the
CPU, 120-token prompt and 8 decode steps (256 decisions), seeds 0-15:
sound runs read logit differences of at most 0.0441, margins of at most
2.43e-3 and at most 4 decisions of 256 differing; the reference with its
experts in float8_e4m3 differs by 0.117 or more, a far expert has a margin of
0.16 or more, exchanged positions 0.13, a router in float8 9.3e-3 to 2.3e-2.
The old comparison (the reference under its OWN choice) reads 0.83 to 1.79
on five of those sixteen sound runs.
"""

import json
import time

import numpy as np
import pytest

import routed_handover
from perfbench import manifest, traffic
from perfbench.families import olmoe as family
from perfbench.reference import olmoe_ref

PROMPT, STEPS, POSITIONS = 120, 8, 160
LIMITS = {"logit_atol": 0.085, "why_logit_atol": "sound 0.0441, float8 0.117",
          "route_margin": 0.0075,
          "why_route_margin": "three times the 2.43e-3 seen",
          "route_differing_share": 0.047,
          "why_route_differing_share": "three times the 4 of 256 seen"}


def _trained_scale(init):
    """init_params at 0.02 -> streams of unit variance, strong experts."""
    import jax

    def scaled(rng, cfg):
        def one(path, leaf):
            key = jax.tree_util.keystr(path)
            if "norm" in key:
                return leaf
            return leaf * (50.0 if "wte" in key else
                           25.0 if "w_down" in key else 6.25)
        return jax.tree_util.tree_map_with_path(one, init(rng, cfg))
    return scaled


@pytest.fixture
def program(monkeypatch):
    """``install(fault)``: llama:tiny-moe-long registered, its weights at a
    trained scale, the hand-over laid over the runner."""
    from ray_tpu.models import llama
    monkeypatch.setitem(llama.PRESETS, "tiny-moe-long",
                        lambda: llama.tiny_moe(seq=POSITIONS))
    monkeypatch.setattr(llama, "init_params",
                        _trained_scale(llama.init_params))
    return lambda fault=None: routed_handover.install(monkeypatch, fault)


def _ctx(seed: int) -> dict:
    """The job's context as run.prepare builds it, for llama:tiny-moe."""
    from ray_tpu.models import llama
    tiny = llama.tiny_moe(seq=POSITIONS)
    config = {"family": "olmoe",
              **{k: getattr(tiny, attr) for k, attr in family.ATTRS.items()},
              "router_aux_loss_coef": 0.01, "router_z_loss_coef": 0.001,
              "serve": {"engine": {
                  "model": "llama:tiny-moe-long", "max_model_len": POSITIONS,
                  "max_num_seqs": 4, "num_blocks": 64, "block_size": 8,
                  "max_prefill_tokens": POSITIONS,
                  "prefill_len_buckets": [16, 32, POSITIONS],
                  "decode_batch_buckets": [4], "share_weights": False},
                  **LIMITS}}
    over = json.loads((manifest.BENCH_DIR / "rehearsal" / "overrides.json")
                      .read_text())
    spec = json.loads((manifest.BENCH_DIR / "traffic" /
                       "serve-chat-busy.json").read_text())
    return {"config_file": config,
            "traffic_file": {**spec, **over["traffic"]["serve"],
                             "check_prompt_tokens": PROMPT,
                             "check_decode_steps": STEPS},
            "seed": seed, "seconds": 0.5, "trace": False, "notes": True,
            "marks": {}, "t_start": time.perf_counter()}


def _checked(seed: int, also=None):
    from perfbench.jobs import serve
    served = serve.Served(_ctx(seed))
    try:
        check = served.check_logits(seed)
        return (check, also(served, seed)) if also else check
    finally:
        served.close()


def old_comparison(served, seed: int) -> float:
    """``check_logits`` as it was before it took the program's choice: the
    same prompt through the same calls, against the reference's forward
    under its OWN top-k; the largest absolute logit difference."""
    eng, spec = served.eng, served.spec
    runner, cache = eng.runner, eng.cache
    n, k = spec["check_prompt_tokens"], spec["check_decode_steps"]
    prompt = [int(t) for t in traffic.rng_for(seed, "serve_check")
              .integers(0, served.config["vocab_size"], n)]
    cache.alloc_seq("pb_old", n)
    try:
        logits, ks, vs = runner.prefill(prompt)
        cache.scatter_prefill("pb_old", np.asarray(ks, np.float32),
                              np.asarray(vs, np.float32), n)
        got, seq = [logits], list(prompt)
        for _ in range(k):
            seq.append(int(np.argmax(got[-1])))
            blk, off, _ = cache.append_slot("pb_old")
            tables = np.zeros((1, served.ecfg.max_blocks_per_seq), np.int32)
            table = cache.table("pb_old")
            tables[0, :len(table)] = table
            at = np.asarray([len(seq) - 1], np.int32)
            lg, ks, vs = runner.decode(np.asarray([seq[-1]], np.int32), at,
                                       cache.pool, tables, at)
            cache.write_token(blk, off, np.asarray(ks[:, 0], np.float32),
                              np.asarray(vs[:, 0], np.float32))
            got.append(lg[0])
    finally:
        cache.free_seq("pb_old")
    ref = np.asarray(served.fam.reference_logits(
        served.params, [seq], served.config))[0]
    return max(float(np.abs(g - ref[n - 1 + i]).max())
               for i, g in enumerate(got))


# ---------------------------------------------------------------- sound runs
def test_the_job_runs_the_routed_family_and_its_check_passes(program):
    """Served(ctx) -> the window -> check_logits, as for any family."""
    from perfbench.jobs import serve
    program()
    facts = serve.run(_ctx(seed=2 ** 31 + 5))
    assert facts["correct"] and facts["failed"] == 0
    assert facts["attempted"] > 0 and facts["out_tokens"] > 0
    notes = facts["notes"]
    assert 0 < notes["prefill_logit_diff"] < notes["logit_atol"]
    assert 0 < notes["decode_logit_diff"] < notes["logit_atol"]
    # every position of the prompt and of the steps, in each routed layer
    assert notes["route_decisions"] == 2 * (PROMPT + STEPS)
    assert notes["route_worst_margin"] <= notes["route_margin"] == 0.0075
    assert notes["route_differing_share"] == 0.047


@pytest.mark.parametrize("seed", [0, 1, 6, 11, 14])
def test_a_choice_a_rounding_away_passes(program, seed):
    """bfloat16 decides some of the reference's near ties the other way
    (here seeds 1, 6 and 11 did: 4, 3 and 4 decisions of 256).  Under the
    program's choice the logits agree as closely as where nothing differs;
    the old comparison fails nowhere but where a decision differs, and
    where none does it reads what the new one reads."""
    program()
    check, old = _checked(seed, also=old_comparison)
    assert check["ok"], check
    assert check["route_decisions"] == 2 * (PROMPT + STEPS)
    new = max(check["prefill_logit_diff"], check["decode_logit_diff"])
    assert new < 0.06
    if check["route_differing"]:
        assert 0 < check["route_worst_margin"] < LIMITS["route_margin"]
    else:
        assert check["route_worst_margin"] == 0.0 and old == new
    if old > LIMITS["logit_atol"]:
        assert check["route_differing"] > 0


def test_the_old_comparison_fails_on_a_run_that_the_new_one_passes(program):
    """Among a few seeds at least one run whose choice differs from the
    reference's own, which today's check would have refused."""
    program()
    refused = []
    for seed in (1, 6, 8, 10, 11):
        check, old = _checked(seed, also=old_comparison)
        assert check["ok"], (seed, check)
        if check["route_differing"] and old > 5 * LIMITS["logit_atol"]:
            refused.append(seed)
    assert refused


# ------------------------------------------------ whatever is not a rounding
def _fp8_experts(params):
    import jax.numpy as jnp
    low = jnp.float8_e4m3fn
    experts = {k: v.astype(low).astype(v.dtype)
               for k, v in params["blocks"]["experts"].items()}
    return {**params, "blocks": {**params["blocks"], "experts": experts}}


# fault -> the numbers of the check that it has to put outside their limits
FAILS_BY = {"fp8_experts": {"logits"}, "far_expert": {"margin"},
            "exchanged": {"margin"}, "one_expert_short": {"logits"},
            "renormalised": {"logits"}, "fp8_router": {"margin"}}


@pytest.mark.parametrize("fault", sorted(FAILS_BY))
def test_the_check_fails_on(program, fault):
    """The control (the reference's expert matrices in float8_e4m3, the
    precision below the one served) and each departure of the program that
    is not a rounding: a far expert chosen, two positions' choices
    exchanged, k - 1 experts computed, the weights renormalised, the router
    in float8.  Each planted where the choice is made."""
    from perfbench.jobs import serve
    program(None if fault == "fp8_experts" else fault)
    served = serve.Served(_ctx(seed=3))
    try:
        if fault == "fp8_experts":
            # the reference reads served.params, the program runner.params
            served.params = _fp8_experts(served.params)
        check = served.check_logits(3)
    finally:
        served.close()
    assert not check["ok"], check
    outside = set()
    if max(check["prefill_logit_diff"],
           check["decode_logit_diff"]) > check["logit_atol"]:
        outside.add("logits")
    if check["route_worst_margin"] > check["route_margin"]:
        outside.add("margin")
    if check["route_differing"] > check["route_differing_share"] \
            * check["route_decisions"]:
        outside.add("differing")
    assert outside >= FAILS_BY[fault], (outside, check)
    if fault == "fp8_router":
        # and more decisions differ than in any sound run seen (4 of 256)
        assert check["route_differing"] > 4


# --------------------------------------------- the reference under a choice
def test_a_near_tie_decided_the_other_way_is_a_correct_execution():
    """Two experts the reference scores ~1e-4 apart (one router column all
    but copied from another).  A 'program' that is the float32 reference
    itself, but takes the second where the first is the reference's last
    pick, differs from the reference's own forward by a whole expert and
    from the reference under ITS choice by nothing; the audit counts those
    decisions and their margin is the tie's."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import llama
    cfg = llama.tiny_moe(seq=POSITIONS)
    params = _trained_scale(llama.init_params)(jax.random.key(5), cfg)
    router = params["blocks"]["router"]["kernel"]           # (L, E, X)
    # in the last layer, so that no later decision follows the flipped ones
    nudge = 2e-5 * jax.random.normal(jax.random.key(6), router[1, :, 0].shape)
    router = router.at[1, :, 1].set(router[1, :, 0] + nudge)
    params = {**params, "blocks": {**params["blocks"],
                                   "router": {"kernel": router}}}
    settings = {k: getattr(cfg, attr) for k, attr in family.ATTRS.items()}
    tokens = np.random.default_rng(7).integers(0, cfg.vocab_size, (1, 96))

    own, route = [], olmoe_ref._route

    def recording(*args, **kwargs):
        z, gates, balance, z_loss = route(*args, **kwargs)
        own.append(np.asarray(jax.lax.top_k(gates, kwargs["k"])[1]))
        return z, gates, balance, z_loss

    olmoe_ref._route = recording
    try:
        under_own = np.asarray(olmoe_ref.logits(params, tokens, settings))
    finally:
        olmoe_ref._route = route
    own = np.stack(own)                                     # (L, N, k)
    # expert 0 the last pick, expert 1 just under the cut: take 1 for 0
    last = own[1, :, -1]
    flipped = (last == 0) & ~(own[1] == 1).any(-1)
    assert flipped.sum() >= 2
    chosen = own.copy()
    chosen[1, flipped, -1] = 1
    program_logits, _ = olmoe_ref.logits(params, tokens, settings,
                                         choices=chosen)
    program_logits = np.asarray(program_logits)
    # the old comparison: a whole expert apart
    assert np.abs(program_logits - under_own).max() > 0.5
    # the new one: nothing, and the audit says how near the ties were
    again, audit = olmoe_ref.logits(params, tokens, settings, choices=chosen)
    assert np.abs(program_logits - np.asarray(again)).max() == 0.0
    assert audit["decisions"] == 2 * 96
    assert audit["differing"] == int(flipped.sum())
    assert 0 < audit["worst_margin"] < 1e-3
    # under its own choice the reference is what it is without one
    same, none = olmoe_ref.logits(params, tokens, settings, choices=own)
    assert np.abs(np.asarray(same) - under_own).max() < 1e-5
    assert none == {"decisions": 192, "differing": 0, "worst_margin": 0.0}
    with pytest.raises(ValueError, match="choices of shape"):
        olmoe_ref.logits(params, tokens, settings, choices=own[:, :5])


# ------------------------------------------------------ what the job refuses
@pytest.mark.parametrize("key", ["route_margin", "why_route_margin",
                                 "route_differing_share",
                                 "why_route_differing_share"])
def test_a_routed_configuration_without_its_limits_is_refused(program, key):
    from perfbench.jobs import serve
    program()
    ctx = _ctx(seed=1)
    del ctx["config_file"]["serve"][key]
    with pytest.raises(ValueError, match=key.removeprefix("why_")):
        serve.Served(ctx)


def test_a_program_that_says_nothing_of_its_choice_is_refused(
        program, monkeypatch):
    from perfbench.jobs import serve
    from ray_tpu.serve.llm.model_runner import ModelRunner
    program()
    init = ModelRunner.__init__

    def silent(self, *args, **kwargs):
        init(self, *args, **kwargs)
        self.route_spec = None

    monkeypatch.setattr(ModelRunner, "__init__", silent)
    with pytest.raises(RuntimeError, match="route_spec"):
        serve.Served(_ctx(seed=1))


def test_choices_of_another_shape_or_range_are_refused(program, monkeypatch):
    from perfbench.jobs import serve
    program()
    served = serve.Served(_ctx(seed=1))
    try:
        runner = served.eng.runner
        good = np.zeros((2, 4, 2), np.int32)
        for bad, what in ((good[:1], "not 1 rows"), (good[:, :, :1], "not 1"),
                          (good.astype(np.float32), "float32"),
                          (good + 8, "outside 0..7"), (good - 1, "outside")):
            runner.choices = bad
            with pytest.raises(ValueError, match=what):
                served._choices(1)
        runner.choices = good
        assert served._choices(3).shape == (2, 3, 2)
    finally:
        served.close()


# ----------------------------------------------- a family that does not route
def test_a_dense_familys_check_makes_exactly_todays_calls(monkeypatch):
    """gpt2:tiny (what a rehearsal serves): the family is asked for its
    reference once, with (params, [seq], config) and no choice; the runner
    is not asked for one; the check's numbers are the four it had."""
    from perfbench import run
    from perfbench.families import gpt2
    from perfbench.jobs import serve
    from ray_tpu.serve.llm.model_runner import ModelRunner
    cell = run._rehearsal_cell(manifest.load_cell(
        manifest.load_manifest(), "gpt2-xl-1558m.serve-chat-steady"))
    calls, reference_logits = [], gpt2.reference_logits

    def recording(*args, **kwargs):
        calls.append((len(args), sorted(kwargs)))
        return reference_logits(*args, **kwargs)

    def asked(self):
        raise AssertionError("a dense model's runner was asked its choice")

    monkeypatch.setattr(gpt2, "reference_logits", recording)
    # (a runner may set the attribute; only reading it is the check's)
    monkeypatch.setattr(ModelRunner, "choices",
                        property(asked, lambda self, value: None),
                        raising=False)
    served = serve.Served({**cell, "seed": 4, "seconds": 0.5, "trace": False,
                           "marks": {}, "t_start": time.perf_counter()})
    try:
        assert served.routed is None
        check = served.check_logits(4)
    finally:
        served.close()
    assert calls == [(3, [])]
    assert sorted(check) == ["decode_logit_diff", "logit_atol", "ok",
                             "prefill_logit_diff"]
    assert check["ok"]
