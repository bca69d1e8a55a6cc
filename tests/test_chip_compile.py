"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

No chip is attached: the TPU compiler installed with jax compiles for a
*described* ``v5e:2x2`` device (on-chip-measurement guide, section 2), so
what Mosaic refuses — a misaligned slice, too much VMEM — fails here and
costs no chip time.  Nothing runs; a compile that passes is not a chip
run.  The whole GPT-2-124M step (~20 s) is left to ``chip_smoke.py``.
"""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from ray_tpu.ops.flash_attention import flash_attention  # noqa: E402


@pytest.fixture(scope="module")
def v5e():
    """Sharding on one described v5e chip; the persistent compile cache
    is off meanwhile (an entry written for an absent chip cannot be read
    back, and the next compile would warn about it)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes_dtypes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes_dtypes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text        # Mosaic, not interpret mode
    return text


def _kernel_results(text):
    """The first result of every Mosaic kernel of a compiled module, as
    the chip's trace prints it beside the kernel's name."""
    return re.findall(r"= \(?(bf16\[[\d,]+\])\S* .*custom-call\(.*"
                      r'custom_call_target="tpu_custom_call"', text)


def _kernel_names_and_results(text):
    """(instruction name, first result) of every Mosaic kernel of a
    compiled module: what a reducer of ``perfbench`` matches a kernel by
    (``gmm.37``, ``bf16[98304,2048]``; ``delta_rule_solve.1``,
    ``f32[32,128,64,128]``)."""
    return re.findall(r"%(\S+) = \(?(\w+\[[\d,]+\])\S* .*custom-call\(.*"
                      r'custom_call_target="tpu_custom_call"', text)


def _under_scope(text, scope):
    """(instruction name, first result) of everything a compiled module
    holds under a ``jax.named_scope``, as ``scope_ms_per_step`` joins a
    trace with the program's op map."""
    from ray_tpu.util import tracing
    return [(name, op["shape"]) for name, op in tracing.op_map(text).items()
            if scope in op.get("scope", "")]


def _assert_the_sorted_passes_are_only_the_layers_work(text, rows, slots):
    """What ``ops/moe.dropless_experts`` spares the compiler by telling it
    what the sort makes true (PR 49), read off a compiled module: the
    gathers promise their indices, so no ``select`` fills a row of the
    ``rows`` sorted (the shape as HLO prints it) under the dispatch's or
    the combine's scope; the k slots lead the rows gathered back, so no
    (N, k, d) array ``slots`` exists, which in a tiled layout is a copy of
    all N k rows; the counts are a compare and a sum and the weights'
    gradient a sort, so the dispatch holds no scatter."""
    assert slots not in text
    under = [line for line in text.splitlines()
             if "moe_dispatch" in line or "moe_combine" in line]
    assert len(under) > 20, len(under)
    assert not [line for line in under
                if re.search(rf"= {re.escape(rows)}\S* select\(", line)]
    assert not [line for line in under
                if "scatter" in line and "moe_dispatch" in line]


def _assert_no_layers_experts_are_made(text, shapes, also=()):
    """What ``ops/moe._megablox_at`` spares a training step (PR 66), read
    off a compiled module: the grouped matmuls read a layer's experts in
    their stack, in place, so nothing in either loop has a layer's expert
    matrix (one of ``shapes``, as HLO prints them) as its result but the
    weights' gradient: a ``tgmm`` kernel, and where the compiler moves that
    result to another memory on its way into the gradients' stack, the
    ``ConcatBitcast`` of its slices.  The parent's scans sliced the layer
    out of the stack for the kernels, a ``dynamic-slice_bitcast_fusion``
    each, three a loop, and copied some of those again.  A fused
    computation's ``parameter`` names a shape and makes nothing; ``also``:
    opcodes a cell's other operations of that shape have."""
    made = [(m.group(1), m.group(3), m.group(0)) for shape in shapes
            for m in re.finditer(
                rf"%(\S+) = ({re.escape(shape)})\S* ([\w-]+)\(.*", text)]
    kernels = [name for name, op, line in made
               if op == "custom-call" and "tpu_custom_call" in line]
    assert kernels and all(k.split(".")[0] == "tgmm" for k in kernels), made
    moved = [line for _, op, line in made
             if op == "custom-call" and "tpu_custom_call" not in line]
    assert all('custom_call_target="ConcatBitcast"' in line
               and "slice-done" in line for line in moved), moved
    rest = {op for _, op, _ in made} - {"custom-call", "parameter", *also}
    assert not rest, [(name, op) for name, op, _ in made if op in rest]


def _assert_the_router_picks_in_vmem(text, tokens, experts, calls):
    """What ``ops/moe.choose_experts`` spares a step (PR 68), read off a
    compiled module: the k experts a token are picked by ``calls`` Mosaic
    kernels named ``router_choice`` whose first result, the ids as whole
    sublane tiles of (k, N), is a shape ``moe.router_choice_ms`` knows them
    by; nothing sorts the (N, E) scores (the parent: ``sort.382`` /
    ``.392``, ``(f32[16384,128], s32[16384,128])``, ``lax.top_k``), nothing
    under the router's scope gathers (``take_along_axis``: 98,304 single
    numbers a call) and nothing scatters into an array the scores' size
    (their derivative: ``scatter.94`` into ``f32[2097152]``)."""
    import json
    from pathlib import Path
    keyed = json.loads((Path(__file__).parent.parent / "perfbench"
                        / "layer_metrics" / "moe.router_choice_ms.json"
                        ).read_text())["params"]
    picked = [shape for name, shape in _kernel_names_and_results(text)
              if any(part in name for part in keyed["names"])]
    assert len(picked) == calls and set(picked) <= set(keyed["shapes"]), \
        picked
    scores = (f"f32[{tokens},{experts}]", f"s32[{tokens},{experts}]",
              f"f32[{tokens * experts}]")
    lines = text.splitlines()
    for op in ("sort", "scatter"):
        over = [line[:160] for line in lines if f" {op}(" in line
                and any(s in line.split(f" {op}(")[0] for s in scores)]
        assert not over, over
    under = [line for line in lines
             if re.search(r'op_name="[^"]*\brouter\b', line)]
    assert len(under) > 50, len(under)
    assert not [line[:160] for line in under
                if re.search(r" (sort|scatter|gather)\(", line)]


def _flash(q, k, v):
    return flash_attention(q, k, v, True, None, False)


def _flash_loss(q, k, v):
    return _flash(q, k, v).astype(jnp.float32).sum()


TRAIN_QKV = ((32, 1024, 12, 64), jnp.bfloat16)


@pytest.mark.parametrize("fn", [
    pytest.param(_flash, id="forward"),
    pytest.param(jax.grad(_flash_loss, argnums=(0, 1, 2)),
                 id="forward_backward"),
])
def test_flash_attention_at_train_shape(v5e, fn):
    _compile(fn, v5e, TRAIN_QKV, TRAIN_QKV, TRAIN_QKV)


@pytest.mark.parametrize("bucket,dtype", [(64, jnp.bfloat16),
                                          (512, jnp.float32)])
def test_flash_attention_at_prefill_bucket(v5e, bucket, dtype):
    qkv = ((1, bucket, 12, 64), dtype)
    _compile(_flash, v5e, qkv, qkv, qkv)


def test_the_op_map_puts_the_flash_kernels_under_their_scope(v5e):
    """The trace names a Pallas kernel ``tpu_custom_call.<n>``; the op map
    says which is which: ``attn/flash_fwd`` forward, ``attn/flash_bwd``
    backward, with ``jax_include_full_tracebacks_in_locations`` off as the
    programs run.  (Inside a whole step's layer scan the compiler leaves
    the custom call without metadata and its get-tuple-elements carry
    ``pallas_call``: there the map takes the scope from those users.)"""
    from ray_tpu.util import tracing
    jax.config.update("jax_include_full_tracebacks_in_locations", False)

    def loss(q, k, v):
        with jax.named_scope("attn"):
            return _flash_loss(q, k, v)

    ops = tracing.op_map(_compile(jax.grad(loss, argnums=(0, 1, 2)), v5e,
                                  TRAIN_QKV, TRAIN_QKV, TRAIN_QKV))
    kernels = {name: e for name, e in ops.items()
               if name.startswith("tpu_custom_call")}
    assert len(kernels) == 2, sorted(kernels)
    for entry in kernels.values():
        assert entry["scope"].split("/")[0] == "attn", entry
        assert entry["prim"] == "pallas_call"
        assert entry["src"].startswith(("test_chip_compile.py:",
                                        "flash_attention.py:"))
    assert sorted((e["scope"], e["pass"]) for e in kernels.values()) == [
        ("attn/flash_bwd", "bwd"), ("attn/flash_fwd", "fwd")]


# ------------------------------------ GPT-2 XL: heads of 64 left unsplit
def _pairs(qkv):
    from ray_tpu.ops.flash_attention import flash_attention_pairs
    return flash_attention_pairs(qkv, qkv.shape[-1] // 64, None, False)


def _pairs_loss(qkv):
    return _pairs(qkv).astype(jnp.float32).sum()


@pytest.mark.parametrize("fn,batch,seq_len,heads", [
    pytest.param(_pairs, 8, 1024, 25, id="xl_forward"),
    pytest.param(jax.grad(_pairs_loss), 8, 1024, 25,
                 id="xl_forward_backward"),
    pytest.param(jax.grad(_pairs_loss), 32, 1024, 12,
                 id="small_forward_backward"),
    pytest.param(_pairs, 1, 16, 25, id="xl_prefill_bucket_16"),
    pytest.param(_pairs, 1, 128, 25, id="xl_prefill_bucket_128"),
    pytest.param(jax.grad(_pairs_loss), 1, 8192, 25, id="xl_8192_positions"),
])
def test_flash_attention_pairs_compiles(v5e, fn, batch, seq_len, heads):
    """The kernels that read GPT-2's fused projection with its heads
    unsplit, at the XL cell's training shape, at GPT-2 small's, at the XL
    serving cell's smallest and commonest prefill buckets and at a length
    whose whole-sequence blocks need more than Mosaic's default VMEM.  25
    heads are 12.5 pairs: the thirteenth 128-lane block of E = 1,600 is
    half past the array, which Mosaic takes as a partial edge block."""
    text = _compile(fn, v5e, ((batch, 3, seq_len, heads * 64), jnp.bfloat16))
    assert "flash_fwd_pairs" in text


# ------------------------------------------------- OLMoE's training cell
OLMOE_QKV = ((2, 4096, 16, 128), jnp.bfloat16)      # 16 heads x 128, T 4096


@pytest.mark.parametrize("fn", [
    pytest.param(_flash, id="forward"),
    pytest.param(jax.grad(_flash_loss, argnums=(0, 1, 2)),
                 id="forward_backward"),
])
def test_flash_attention_at_olmoe_train_shape(v5e, fn):
    """One head's whole K and V (forward) and q, dO, dq (backward) are
    (4096, 128) blocks here: 1 MB an operand before double buffering."""
    _compile(fn, v5e, OLMOE_QKV, OLMOE_QKV, OLMOE_QKV)


# ------------------------------------ Kanana's training cell: five operands
def _gqa_flash_loss(q, k, v):
    """16 query heads on 2 K/V heads repeated, as models/qwen3_next.py."""
    k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2) for x in (k, v))
    return _flash_loss(q, k, v)


@pytest.mark.parametrize("fn", [_flash, jax.grad(_gqa_flash_loss,
                                                 argnums=(0, 1, 2))],
                         ids=["fwd", "bwd"])
def test_flash_attention_at_qwen3_next_train_shape(v5e, fn):
    """D = 256 at 8,192 positions (Kanana's 192-wide keys pad to 256
    lanes, its values are 128): 8 MiB of resident keys and values a grid
    row, under a raised scoped limit; the kernels' first results are what
    ``attn.gated_flash_ms`` is keyed on."""
    q = ((2, 8192, 16, 256), jnp.bfloat16)
    kv = q if fn is _flash else ((2, 8192, 2, 256), jnp.bfloat16)
    text = _compile(fn, v5e, q, kv, kv)
    assert "bf16[32,8192,256]" in _kernel_results(text)


def _rule_loss(q, k, v, g, beta):
    from ray_tpu.ops.delta_rule import gated_delta_rule
    with jax.named_scope("gdn_rule"):
        o, _ = gated_delta_rule(q, k, v, g, beta)
    return o.astype(jnp.float32).sum()


def _rule_loss_qkv(qkv, g, beta):
    """The rule as ``models/qwen3_next._gdn_mixer`` calls it, on the
    conv's output whole and unnormed, under the scope ``gdn.rule_ms``
    reads."""
    from ray_tpu.ops.delta_rule import gated_delta_rule_qkv
    with jax.named_scope("gdn_rule"):
        o, _ = gated_delta_rule_qkv(qkv, g, beta, 16, 128)
    return o.astype(jnp.float32).sum()


_RULE_Q_K_V = [(2, 8192, 16, 128)] * 2 + [(2, 8192, 32, 128)]
_RULE_QKV = [(2, 8192, 8192)]


@pytest.mark.parametrize("loss,operands,dtype", [
    pytest.param(_rule_loss_qkv, _RULE_QKV, jnp.bfloat16,
                 id="bf16_whole_in_kernels"),
    pytest.param(_rule_loss, _RULE_Q_K_V, jnp.bfloat16,
                 id="bf16_in_kernels"),
    pytest.param(_rule_loss_qkv, _RULE_QKV, jnp.float32,
                 id="float32_in_xla")])
def test_the_delta_rule_compiles_at_qwen3_next_train_shape(v5e, monkeypatch,
                                                           loss, operands,
                                                           dtype):
    """``ops/delta_rule.gated_delta_rule_qkv`` (the cell's call: the
    conv's (2, 8192, 8192) output whole) and ``gated_delta_rule`` (three
    normed arrays) forward and backward at 2 x 8,192 positions, 16 key and
    32 value heads of 128, on a TPU.  In bf16 four Mosaic kernels under
    their own names, which ``gdn.rule_kernel_ms`` reads and the
    ``tpu_custom_call`` metrics do not: the solve, what follows it, and
    each one's backward; no scan is left, of a chunk's (64, 64) matrices
    only the solved systems ``T`` and their cotangent reach HBM, the R = 2
    heads' side by side in 128 lanes (``f32[32,128,64,128]``, 134 MB), and
    the temporaries stay under the 1.5 GB that the XLA form's spans were
    held to (the states that enter the 128 chunks, kept for the backward,
    are 537 MB of them).  Of the conv's whole output the kernels read q,
    k and v where they lie and norm what they load: under ``gdn_rule``
    XLA makes no float32 array the size of q (the parent's l2 norms:
    9.5 ms a step) and no slice the size of v (2.2 ms), and puts the
    cotangent together in one fusion.  Float32 activations take the XLA
    form at the same shape: no kernel, two scans each both ways, a span's
    solved systems, and XLA's norms round them."""
    import json
    from pathlib import Path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shapes = [(s, dtype) for s in operands] \
        + [((2, 8192, 32), jnp.float32)] * 2
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(len(args))))) \
        .lower(*args).compile()
    text = compiled.as_text()
    under_rule = _under_scope(text, "gdn_rule")
    assert len(under_rule) > 20
    xlas_norms = [name for name, shape in under_rule
                  if shape == "f32[2,8192,2048]"]
    if dtype == jnp.float32:
        assert "tpu_custom_call" not in text
        assert text.count(" while(") >= 4          # two scans, each both ways
        assert "f32[2,16,2,16,64,64]" in text      # a span's solved systems
        assert xlas_norms
        return
    kernels = _kernel_names_and_results(text)
    assert sorted((name.split(".")[0], shape) for name, shape in kernels) == [
        ("delta_rule_bwd", "bf16[2,8192,2048]"),
        ("delta_rule_fwd", "bf16[2,8192,4096]"),
        ("delta_rule_solve", "f32[32,128,64,128]"),
        ("delta_rule_solve_bwd", "bf16[2,8192,2048]")], kernels
    spec = json.loads((Path(__file__).parent.parent / "perfbench"
                       / "layer_metrics" / "gdn.rule_kernel_ms.json"
                       ).read_text())["params"]
    for name, shape in kernels:
        assert shape in spec["shapes"]
        assert any(part in name for part in spec["names"])
        assert "tpu_custom_call" not in name
    assert " while(" not in text
    assert not re.search(r"f32\[[\d,]*,64,64\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
    assert not xlas_norms
    assert not [name for name, shape in under_rule
                if name.startswith("slice") and shape == "bf16[2,8192,4096]"]
    if loss is _rule_loss_qkv:      # the cotangent of qkv: one fusion
        assert len([name for name, shape in under_rule if "fusion" in name
                    and shape == "bf16[2,8192,8192]"]) == 1, under_rule


def _conv_loss(x, w):
    """The mixer's conv, its silu and slices into q | k | v that are read
    apart, as ``models/qwen3_next._gdn_mixer`` reads them."""
    from ray_tpu.ops.ssm import causal_conv_silu
    y = causal_conv_silu(x, w).astype(jnp.float32)
    return y[..., :2048].sum() + 2 * y[..., 2048:4096].sum() \
        + 3 * y[..., 4096:].sum()


@pytest.mark.parametrize("dtype", [
    pytest.param(jnp.bfloat16, id="bf16_in_kernels"),
    pytest.param(jnp.float32, id="float32_in_xla")])
def test_the_fused_conv_compiles_at_qwen3_next_train_shape(v5e, monkeypatch,
                                                           dtype):
    """``ops/ssm.causal_conv_silu`` forward, and forward and backward, at
    2 x 8,192 positions of 8,192 channels and 4 taps, on a TPU.  In bf16
    (the cell) exactly ``causal_conv_fwd`` and ``causal_conv_bwd`` under
    their own names, which ``gdn.conv_kernel_ms`` reads and the
    ``tpu_custom_call`` metrics do not, their first results in that
    metric's ``shapes``; no float32 array of the sequence's size is left
    (the padded copy and the product XLA's form wrote), and what is kept
    for the backward is x alone.  Float32 activations take the XLA form at
    the same shape: no kernel."""
    import json
    from pathlib import Path
    from ray_tpu.ops.ssm import causal_conv_silu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in
            (((2, 8192, 8192), dtype), ((4, 8192), jnp.bfloat16))]
    texts = [jax.jit(fn).lower(*args).compile().as_text()
             for fn in (causal_conv_silu,
                        jax.grad(_conv_loss, argnums=(0, 1)))]
    if dtype == jnp.float32:
        assert not [t for t in texts if "tpu_custom_call" in t]
        return
    kernels = [_kernel_names_and_results(t) for t in texts]
    assert [[(name.split(".")[0], shape) for name, shape in ks]
            for ks in kernels] == [
        [("causal_conv_fwd", "bf16[2,8192,8192]")],
        [("causal_conv_bwd", "bf16[2,8192,8192]")]], kernels
    spec = json.loads((Path(__file__).parent.parent / "perfbench"
                       / "layer_metrics" / "gdn.conv_kernel_ms.json"
                       ).read_text())["params"]
    for name, shape in kernels[0] + kernels[1]:
        assert shape in spec["shapes"]
        assert any(part in name for part in spec["names"])
        assert "tpu_custom_call" not in name
    for text in texts:
        assert "f32[2,8192,8192]" not in text
        assert "f32[2,8195,8192]" not in text


def _norm_loss(o, z, scale):
    """The mixer's gated output norm under a cotangent that differs by
    head and by lane, as ``gdn_out`` hands one back."""
    from ray_tpu.ops.ssm import gated_rms_norm
    y = gated_rms_norm(o, z, scale, 1e-6).astype(jnp.float32)
    return (y * jnp.arange(4096, dtype=jnp.float32)).sum()


@pytest.mark.parametrize("dtype", [
    pytest.param(jnp.bfloat16, id="bf16_in_kernels"),
    pytest.param(jnp.float32, id="float32_in_xla")])
def test_the_gated_norm_compiles_at_qwen3_next_train_shape(v5e, monkeypatch,
                                                           dtype, capsys):
    """``ops/ssm.gated_rms_norm`` forward, and forward and backward, at
    2 x 8,192 positions of 32 heads of 128, on a TPU.  In bf16 (the cell)
    exactly ``gated_norm_fwd`` and ``gated_norm_bwd`` under their own
    names, which ``gdn.norm_kernel_ms`` reads and the ``tpu_custom_call``
    metrics do not, their first results in that metric's ``shapes``; no
    float32 array of the sequence's size is left, and what is kept for the
    backward is o, z and the scale alone.  Float32 activations take the
    XLA form at the same shape: no kernel."""
    import json
    from pathlib import Path
    from jax.ad_checkpoint import print_saved_residuals
    from ray_tpu.ops.ssm import gated_rms_norm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in
            (((2, 8192, 4096), dtype), ((2, 8192, 4096), dtype),
             ((128,), jnp.float32))]
    texts = [jax.jit(fn).lower(*args).compile().as_text()
             for fn in (lambda o, z, s: gated_rms_norm(o, z, s, 1e-6),
                        jax.grad(_norm_loss, argnums=(0, 1, 2)))]
    if dtype == jnp.float32:
        assert not [t for t in texts if "tpu_custom_call" in t]
        return
    kernels = [_kernel_names_and_results(t) for t in texts]
    assert [[(name.split(".")[0], shape) for name, shape in ks]
            for ks in kernels] == [
        [("gated_norm_fwd", "bf16[2,8192,4096]")],
        [("gated_norm_bwd", "bf16[2,8192,4096]")]], kernels
    spec = json.loads((Path(__file__).parent.parent / "perfbench"
                       / "layer_metrics" / "gdn.norm_kernel_ms.json"
                       ).read_text())["params"]
    for name, shape in kernels[0] + kernels[1]:
        assert shape in spec["shapes"]
        assert any(part in name for part in spec["names"])
        assert "tpu_custom_call" not in name
    for text in texts:
        assert not re.search(r"f32\[2,8192,(4096|32,128)\]", text)
    capsys.readouterr()
    print_saved_residuals(lambda o, z, scale: gated_rms_norm(
        o, z, scale, 1e-6), *args)
    kept = capsys.readouterr().out.splitlines()
    assert [line.split(" from ")[1] for line in kept] == [
        "the argument o", "the argument z", "the argument scale"], kept


def _latent(*parts):
    from ray_tpu.ops.flash_attention import latent_flash_attention
    return latent_flash_attention(*parts, None, False)


def _latent_loss(*parts):
    return _latent(*parts).astype(jnp.float32).sum()


@pytest.mark.parametrize("fn,results", [
    pytest.param(_latent, ["bf16[64,8192,128]"], id="forward"),
    pytest.param(jax.grad(_latent_loss, argnums=(0, 1, 2, 3, 4)),
                 ["bf16[64,8192,128]"] * 2, id="forward_backward"),
])
def test_latent_flash_at_kanana_train_shape(v5e, fn, results):
    """2 x 8,192 positions, 32 heads, q_nope / k_nope / v 128 wide, q_rope
    64 and the rotary key (2, 8192, 64) with no head axis: k_nope and the
    rotary key whole in VMEM are the 4 MiB an operand that the joined
    192-wide keys were (64 lanes pad to 128), q, dO and two dq scratches
    in the backward.  Each kernel's first result is 128 wide, and the
    rotary key enters as it is: no operation makes it 32 heads wide."""
    wide = ((2, 8192, 32, 128), jnp.bfloat16)
    text = _compile(fn, v5e, wide, ((2, 8192, 32, 64), jnp.bfloat16), wide,
                    ((2, 8192, 64), jnp.bfloat16), wide)
    assert _kernel_results(text) == results
    assert not re.search(r"bf16\[2,8192,32,64\]\S* broadcast\(", text)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (a
    loop's body, a branch), each with the jaxpr it stands in."""
    for eqn in jaxpr.eqns:
        yield jaxpr, eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(inner)


def test_flash_backward_tile_is_keys_down_at_kanana_train_shape():
    """``flash_bwd`` as traced at the Kanana cell's shape computes its tile
    keys-down (PR 50): lse and delta are read as the lane vectors they are
    stored as, so no ``transpose`` stands anywhere in the kernel, and of a
    tile's eight products (two parts' scores, dv, dp, two dk, two dq) only
    dq's, one a part, contract dimension 0 of their left operand, all on
    the one ``dsT`` that dk's plain products take too; dv's left operand is
    another array (``pT``).  A later edit cannot bring the second
    transposed (512, 512) tile back unseen.  A trace: no chip described."""
    from ray_tpu.ops.flash_attention import _flash_backward_flat
    parts = 2

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype)
    row = shape(64, 1, 8192, dtype=jnp.float32)
    traced = jax.make_jaxpr(lambda qs, ks, *rest: _flash_backward_flat(
        qs, ks, *rest, causal=True, block_size=None, interpret=False))(
        (shape(64, 8192, 128), shape(64, 8192, 64)),
        (shape(64, 8192, 128), shape(2, 8192, 64)),
        shape(64, 8192, 128), row, row, shape(64, 8192, 128))
    (call,) = [e for e in traced.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "flash_bwd"
    tiles = {}          # the jaxpr a tile stands in -> its products
    for inside, eqn in _equations(call.params["jaxpr"]):
        assert eqn.primitive.name != "transpose", eqn
        if eqn.primitive.name == "dot_general":
            tiles.setdefault(id(inside), []).append(eqn)
    assert len(tiles) == 2              # the diagonal tile, the loop's body
    for products in tiles.values():
        assert len(products) == 3 * parts + 2

        def left_contracts(eqn):
            return eqn.params["dimension_numbers"][0][0]
        transposed = [e for e in products if left_contracts(e) == (0,)]
        assert len(transposed) == parts
        (ds_t,) = {id(e.invars[0]) for e in transposed}
        assert sorted(e.outvars[0].aval.shape for e in transposed) == \
            [(512, 64), (512, 128)]                     # dq's two parts
        plain = [e for e in products if left_contracts(e) == (1,)
                 and e.params["dimension_numbers"][0][1] == (0,)]
        assert len(plain) == parts + 1                  # dk's two, dv
        assert sum(id(e.invars[0]) == ds_t for e in plain) == parts


def _experts(x, w_router, w_gate, w_up, w_down):
    from ray_tpu.ops.moe import dropless_moe_ffn
    return dropless_moe_ffn(x, w_router, w_gate, w_up, w_down, k=8)[0]


def _experts_loss(*args):
    return _experts(*args).astype(jnp.float32).sum()


@pytest.mark.parametrize("fn,n_grouped", [
    pytest.param(_experts, 3, id="forward"),
    # the value too: no gradient reads the experts' output rows, so a
    # function of the gradients alone runs no down projection at all
    pytest.param(jax.value_and_grad(_experts_loss, argnums=(0, 1, 2, 3, 4)),
                 9, id="forward_backward"),
])
def test_dropless_experts_at_olmoe_train_shape(v5e, monkeypatch, fn,
                                               n_grouped):
    """8,192 tokens, 8 of 64 experts each: 65,536 rows in 64 ragged
    groups through 2048 -> 1024 twice and 1024 -> 2048; every grouped
    matmul, forward and both backward products, is a Mosaic kernel
    (megablox at ``GMM_TILING``) whose result has one of the shapes
    ``moe.expert_matmul_ms`` is keyed on, no (N, E, C) dispatch tensor
    is built, and the passes over the sorted rows are the gathers and the
    sums alone."""
    import json
    from pathlib import Path
    # the code under compile asks for the backend and must take the
    # branch it takes on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf = jnp.bfloat16
    text = _compile(fn, v5e, ((8192, 2048), bf), ((2048, 64), bf),
                    ((64, 2048, 1024), bf), ((64, 2048, 1024), bf),
                    ((64, 1024, 2048), bf))
    kernels = re.findall(r"= (bf16\[[\d,]+\])\S* custom-call\(.*"
                         r'custom_call_target="tpu_custom_call"', text)
    assert len(kernels) == n_grouped, kernels
    keyed = json.loads((Path(__file__).parent.parent / "perfbench" /
                        "layer_metrics" / "moe.expert_matmul_ms.json")
                       .read_text())["params"]["shapes"]
    assert set(kernels) <= set(keyed), kernels
    assert "ragged-dot" not in text
    assert not re.search(r"\[8192,64,\d+\]", text)
    _assert_the_sorted_passes_are_only_the_layers_work(
        text, "bf16[65536,2048]", "[8192,8,2048]")


def _grouped_products(rows, w, group_sizes):
    """Forward, the rows' gradient and the weights' gradient of one
    grouped matmul, each under the tile of its own shape."""
    from ray_tpu.ops.moe import grouped_matmul

    def loss(rows, w):
        return grouped_matmul(rows, w, group_sizes).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1))(rows, w)


@pytest.mark.parametrize("m,groups,held,d,f", [
    pytest.param(163840, 512, 64, 2048, 512, id="qwen3_next_gate_and_up"),
    pytest.param(163840, 512, 64, 512, 2048, id="qwen3_next_down"),
    pytest.param(98304, 128, 16, 2048, 768, id="kanana_gate_and_up"),
    pytest.param(98304, 128, 16, 768, 2048, id="kanana_down"),
])
def test_a_whole_expert_matrix_beside_256_rows_fits_the_chip(
        v5e, monkeypatch, m, groups, held, d, f):
    """Where ``gmm_tiling`` answers a group's whole (d, f) matrix beside
    256 rows (PR 62: the Qwen3-Next and Kanana cells), Mosaic takes all
    three kernels within its default scoped VMEM: the forward (recomputed
    under the gradient), the rows' gradient and ``tgmm``, whose float32
    accumulator is the whole matrix."""
    from ray_tpu.ops import moe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.gmm_tiling(m, d, f) == (256, d, f)
    assert moe._gmm_vmem_bytes(256, d, f, 2) <= 15 * 2 ** 20
    text = _compile(_grouped_products, v5e, ((m, d), jnp.bfloat16),
                    ((held, d, f), jnp.bfloat16), ((groups,), jnp.int32))
    assert sorted(_kernel_results(text)) == sorted(
        [f"bf16[{m},{d}]", f"bf16[{held},{d},{f}]"]), text[:2000]


def _relu2_experts_loss(x, w_router, w_up, w_down):
    from ray_tpu.ops.moe import dropless_moe_ffn
    y, _ = dropless_moe_ffn(
        x, w_router, None, w_up, w_down, k=6, scoring="sigmoid",
        select_bias=jnp.zeros((w_router.shape[-1],), jnp.float32),
        weight_scale=2.5)
    return y.astype(jnp.float32).sum()


def test_the_two_matrix_experts_compile_at_nemotron_train_shape(v5e,
                                                                monkeypatch):
    """The Nemotron-H cell's expert layer (PR 73): 16,384 tokens, 6 of 128
    experts each, 16 held, the two-matrix ``relu ** 2`` form at 2,688 <->
    1,856.  No 128-lane tile divides 1,856 (29 x 64), so ``gmm_tiling``
    takes it whole beside 384 of the 2,688 and 512 rows, and Mosaic takes
    all of megablox's kernels at that tile: the up projection forward
    (recomputed under the gradient it would be twice), the hidden rows'
    and the rows' gradients, and the two ``tgmm``, each with the result
    ``moe.relu2_expert_peak_share`` is keyed on; no ``ragged-dot``, and
    the router picks its 6 in the kernel."""
    import json
    from pathlib import Path
    from ray_tpu.ops import moe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, d, f, width, held = 16384, 2688, 1856, 128, 16
    assert moe.gmm_tiling(n * 6, d, f) == (512, 384, 1856)
    assert moe.gmm_tiling(n * 6, f, d) == (512, 1856, 384)
    bf = jnp.bfloat16
    text = _compile(jax.value_and_grad(_relu2_experts_loss,
                                       argnums=(0, 1, 2, 3)),
                    v5e, ((n, d), bf), ((d, width), bf), ((held, d, f), bf),
                    ((held, f, d), bf))
    kernels = _kernel_names_and_results(text)
    keyed = json.loads((Path(__file__).parent.parent / "perfbench"
                        / "layer_metrics" / "moe.relu2_expert_peak_share.json"
                        ).read_text())["params"]
    experts = sorted(shape for name, shape in kernels
                     if shape in keyed["shapes"]
                     and any(part in name for part in keyed["names"]))
    assert experts == sorted(
        ["bf16[98304,1856]"] * 2 + ["bf16[98304,2688]"] * 2
        + ["bf16[16,2688,1856]", "bf16[16,1856,2688]"]), kernels
    assert [shape for name, shape in kernels
            if name.startswith("router_choice")] == ["s32[8,16384]"]
    assert "ragged-dot" not in text


def _scan_loss(x, dt, a, b, c):
    from ray_tpu.ops.ssm import ssd_scan
    y, state = ssd_scan(x, dt, a, b, c, 128)
    return y.sum() + state.sum()


def test_the_scan_and_its_backward_compile_at_nemotron_train_shape(v5e):
    """``ops/ssm.ssd_scan`` at the Nemotron-H cell's shape (PR 73: 2 x 8,192
    positions, 64 heads of 64 on 8 groups of 128 state columns, chunks of
    128) with its first backward: plain XLA, no kernel, and a layer's
    forward and backward hold 2.2e9 B of temporaries (the (2, 64, 128, 128,
    64) float32 decays and their product with the scores, which autodiff
    keeps), which is what a block's recomputation has to fit beside the
    state."""
    f32, bf = jnp.float32, jnp.bfloat16
    shapes = (((2, 8192, 64, 64), bf), ((2, 8192, 64), f32), ((64,), f32),
              ((2, 8192, 8, 128), bf), ((2, 8192, 8, 128), bf))
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    compiled = jax.jit(jax.grad(_scan_loss, argnums=(0, 1, 2, 3, 4))) \
        .lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.6e9


def _train_scan_loss(xbc, dt, a, d):
    """The scan as ``models/nemotron_h._mamba`` calls it, on the conv's
    output whole, under the scope ``ssm.train_scan_ms`` reads."""
    from ray_tpu.ops.ssm import ssd_scan_train
    with jax.named_scope("ssm_scan"):
        return ssd_scan_train(xbc, dt, a, d, 64, 8, 128).sum()


def test_the_training_scans_kernels_compile_at_nemotron_train_shape(
        v5e, monkeypatch):
    """``ops/ssm.ssd_scan_train`` and its backward at the Nemotron-H cell's
    shape (PR 74: the conv's (2, 8192, 6144) float32 result whole, 64
    heads of 64 on 8 groups of 128 state columns, chunks of 128), on a
    TPU: two Mosaic kernels under their own names, both attributed to
    ``ssm_scan``, each within the default scoped VMEM (no limit is
    raised); no scan of XLA's is left and nothing (Q, Q) a head reaches
    HBM; the temporaries, the 268 MB of entering states among them, stay
    far under the 2.2e9 B that ``ssd_scan`` under autodiff holds (the test
    above)."""
    from ray_tpu.ops import ssm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    f32 = jnp.float32
    shapes = (((2, 8192, 6144), f32), ((2, 8192, 64), f32), ((64,), f32),
              ((64,), f32))
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    assert ssm._scan_kernels_run(args[0], 64, 64, 8, 128)
    compiled = jax.jit(jax.grad(_train_scan_loss, argnums=(0, 1, 2, 3))) \
        .lower(*args).compile()
    text = compiled.as_text()
    kernels = _kernel_names_and_results(text)
    assert sorted((name.split(".")[0], shape) for name, shape in kernels) == [
        ("ssd_scan_bwd", "f32[2,8192,4096]"),
        ("ssd_scan_fwd", "f32[2,8192,4096]")], kernels
    under = dict(_under_scope(text, "ssm_scan"))
    assert all(name in under for name, _ in kernels), sorted(under)
    assert " while(" not in text
    assert not re.search(r"f32\[[\d,]*,128,128,64\]", text)    # the decays
    assert "f32[2,8,64,128,512]" in text                # the entering states
    assert compiled.memory_analysis().temp_size_in_bytes < 0.8e9


def _spread(x, order, held_rows):
    from ray_tpu.ops.moe import _spread_rows
    return _spread_rows(x, order, held_rows)


def _sum(rows, inverse, held_rows, *, k):
    from ray_tpu.ops.moe import _sum_slots
    return _sum_slots(rows, inverse, k, held_rows)


@pytest.mark.parametrize("n,k,d,dtype", [
    pytest.param(16384, 6, 2048, jnp.bfloat16, id="kanana_step"),
    # 163,840 assignments: the way back's list is one int32 an entry (two
    # arrays of them are 1.25 MiB of the 1 MiB scalar memory: PR 57)
    pytest.param(16384, 10, 2048, jnp.bfloat16, id="qwen3_next_step"),
    pytest.param(2048, 4, 3072, jnp.bfloat16, id="trinity_chunk"),
    # 8,192 lanes of float32: more than Mosaic's default scoped VMEM
    pytest.param(1024, 2, 8192, jnp.float32, id="wide_float32"),
])
@pytest.mark.parametrize("which", ["spread", "sum"])
def test_held_row_kernels_compile_at_the_cells_shapes(v5e, monkeypatch, which,
                                                      n, k, d, dtype):
    """The two passes that stop at ``held_rows`` (``ops/moe.py``): the way
    out holds its source in VMEM whole (64 MiB at Kanana's shape, under a
    raised scoped limit), the way back fetches a row as the aligned 8 rows
    it lies in (a slice of an HBM array is whole tiles), and both pick the
    row out as 32-bit words through a bitcast reference, none of which
    interpret mode can refuse; each is one Mosaic kernel whose instruction
    carries the wrapping function's name."""
    import functools
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    index, scalar = ((n * k,), jnp.int32), ((), jnp.int32)
    if which == "spread":
        text = _compile(_spread, v5e, ((n, d), dtype), index, scalar)
        name, result = "spread_held_rows", (n * k, d)
    else:
        text = _compile(functools.partial(_sum, k=k), v5e,
                        ((n * k, d), dtype), index, scalar)
        name, result = "sum_held_slots", (n, d)
    (kernel,) = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
    shape = ",".join(map(str, result))
    assert re.search(rf"%{name}\.\d+ = \w+\[{shape}\]", kernel), kernel[:200]
    assert " gather(" not in text


def _choice_loss(keys, *payload, k):
    from ray_tpu.ops.moe import choose_experts
    idx, picked = choose_experts(keys, payload[0] if payload else None, k)
    return picked.sum(), idx


@pytest.mark.parametrize("n,e,k,biased", [
    pytest.param(16384, 512, 10, False, id="qwen3_next_step"),
    pytest.param(16384, 128, 6, True, id="kanana_step"),
    pytest.param(2048, 128, 8, False, id="prefill_chunk"),
])
def test_the_routers_choice_compiles_at_the_cells_shapes(v5e, monkeypatch, n,
                                                         e, k, biased):
    """``ops/moe.choose_experts`` with its gradient: one Mosaic kernel, a
    tile of 256 tokens' (256, E) keys (and payload) turned in VMEM inside
    Mosaic's default scoped limit, its instruction named after the jitted
    function round it and its first result the ids as (k to whole sublane
    tiles, N); the backward a select in XLA: no sort, no gather and no
    scatter anywhere in the program."""
    import functools
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    scores = ((n, e), jnp.float32)
    fn = jax.value_and_grad(functools.partial(_choice_loss, k=k),
                            argnums=int(biased), has_aux=True)
    text = _compile(fn, v5e, *[scores] * (1 + biased))
    (kernel,) = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
    rows = -(-k // 8) * 8
    assert re.search(rf"%router_choice\.\d+ = \(s32\[{rows},{n}\]", kernel), \
        kernel[:200]
    assert "vmem_limit_bytes" not in kernel
    assert not re.search(r" (sort|scatter|gather)\(", text)


# ----------------------------------------------- the serving cell's decode
def _paged(q, kv_pool, layer, tables, lens, k_new, v_new):
    from ray_tpu.ops.paged_attention import _paged_decode_kernel
    return _paged_decode_kernel(q, kv_pool, layer, tables, lens, k_new,
                                v_new)


@pytest.mark.parametrize("heads,kv_heads,head_dim,blocks", [
    pytest.param(25, 25, 64, 128, id="gpt2_xl"),         # 1,600 lanes
    pytest.param(16, 16, 128, 128, id="olmoe"),
    pytest.param(32, 8, 128, 256, id="grouped_query"),
    pytest.param(20, 4, 128, 1024, id="falcon_h1"),      # 5 queries a KV head
])
def test_paged_decode_kernel_at_decode_shape(v5e, heads, kv_heads, head_dim,
                                             blocks):
    """8 slots, a float32 pool of 16-position blocks handed over whole
    (six layers of it, the layer's index a traced scalar), a table of 64
    columns: Mosaic copies whole 128-lane tiles, and the pool is stored
    in them, a position's heads side by side, padded (1,600 -> 1,664
    lanes for XL).  Nothing of the pool's size is made on the way in."""
    from ray_tpu.serve.llm.kv_cache import device_shape
    q = ((8, heads, head_dim), jnp.bfloat16)
    new = ((8, kv_heads, head_dim), jnp.bfloat16)
    pool = device_shape(blocks, 6, 16, kv_heads, head_dim)
    text = _compile(_paged, v5e, q, (pool, jnp.float32), ((), jnp.int32),
                    ((8, 64), jnp.int32), ((8,), jnp.int32), new, new)
    assert "paged_decode" in text
    assert not _made(text, math.prod(pool), math.prod(pool[1:]),
                     math.prod(pool[2:]))


def _made(text, *counts):
    """The float32 arrays of one of these element counts that a compiled
    program makes: ``(opcode, shape)`` of every instruction with such a
    result, a fusion's inner instructions included.  Naming what is
    there makes nothing: parameters, tuple elements, bitcasts."""
    free = ("parameter", "get-tuple-element", "bitcast")
    found = []
    for shape, opcode in re.findall(
            r"= f32\[([\d,]+)\]\S* ([\w-]+)\(", text):
        if opcode not in free and \
                math.prod(int(d) for d in shape.split(",")) in counts:
            found.append((opcode, shape))
    return found


def _assert_the_pool_is_read_in_place_and_written_by_rows(text, pool,
                                                          lanes_used):
    """``pool``: the K/V pool's device shape ``(L, 2, N, bs, F)``, of
    whose F lanes a position uses ``lanes_used``.  The one thing the
    program makes at the pool's size is the row update, a scatter (alone
    in its fusion) over the pool as rows of F lanes, aliased to the
    donated argument; and it makes nothing the size of a layer's K or V
    (N x bs x F as stored, or the N x bs x KV x D in use), or of both: no
    slice, select, copy, transpose or pad on the kernel's way."""
    rows = f"{math.prod(pool[:-1])},{pool[-1]}"
    assert sorted(_made(text, math.prod(pool))) == [
        ("fusion", rows), ("scatter", rows)], _made(text, math.prod(pool))
    assert "input_output_alias={ {0}: (0, {}, may-alias)" in text
    layer = math.prod(pool[2:4])
    sizes = {k * layer * f for k in (1, 2) for f in (pool[-1], lanes_used)}
    assert not _made(text, *sizes), _made(text, *sizes)


def _assert_a_rows_token_may_come_from_the_last_steps_ids(text, bucket):
    """The decode program's operands ``last_ids`` and ``src`` (the ids the
    step before chose and, a row, its row there): ``bucket`` ids are
    looked up under the ``embed`` scope, the ids go out again at the same
    width, and that is all of it (the callers go on to assert that the
    pool is aliased and nothing of its size is made)."""
    entry = text[text.index("ENTRY "):]
    ids = f"s32[{bucket}]"
    assert len(re.findall(rf"= {re.escape(ids)}\S* parameter\(", entry)) >= 5
    picks = [line for line in text.splitlines()
             if "/embed/" in line and f"= {ids}" in line]
    assert picks, "no s32[bucket] operation under the embed scope"
    root = next(line for line in entry.splitlines() if " ROOT " in line)
    assert root.count(ids) >= 2, root       # the ids, and the ids carried


@pytest.mark.parametrize("width,order,copied", [(1600, "{0,1:", True),
                                                (1664, "{1,0:", False)])
def test_the_device_holds_a_table_by_its_shape_not_by_its_use(
        v5e, width, order, copied):
    """What ``_common.serving_params`` rests on.  A table whose ONLY use
    is a gather of rows is still held in column order at GPT-2 XL's
    width (1,600 = 12.5 x 128 lanes: the compact order) and copied whole
    to row order in every run; a second leaf of that shape would buy
    nothing.  With its rows padded to whole lanes it is held in row
    order and read in place."""
    compiled = jax.jit(lambda table, ids: table[ids]).lower(
        jax.ShapeDtypeStruct((50257, width), jnp.bfloat16, sharding=v5e),
        jax.ShapeDtypeStruct((8,), jnp.int32, sharding=v5e)).compile()
    text = compiled.as_text()
    entry = text[text.index("ENTRY "):]
    table = next(l for l in entry.splitlines() if " parameter(0)" in l)
    assert f"= bf16[50257,{width}]{order}" in table, table
    assert bool(re.search(rf"= bf16\[50257,{width}\]\S* copy\(", text)) \
        == copied
    assert (compiled.memory_analysis().temp_size_in_bytes > 160e6) == copied


@pytest.fixture(scope="module")
def xl_runner(v5e):
    """The XL cell's runner over the weights as it prepares them, as
    shapes on the chip: bf16, and ``wte`` a second time for ``_embed``'s
    gather, its 1,600-wide rows padded to 13 x 128 lanes."""
    import json
    from pathlib import Path

    from ray_tpu.models._common import serving_params
    from ray_tpu.serve.llm import EngineConfig
    from ray_tpu.serve.llm.config import resolve_model
    from ray_tpu.serve.llm.kv_cache import device_shape
    from ray_tpu.serve.llm.model_runner import ModelRunner
    engine = json.loads((Path(__file__).parent.parent / "perfbench" /
                         "configs" / "gpt2-xl-1558m.json").read_text()
                        )["serve"]["engine"]
    for key in ("decode_batch_buckets", "prefill_len_buckets"):
        engine[key] = tuple(engine[key])
    ecfg = EngineConfig(**engine)
    mod, mcfg = resolve_model(ecfg)
    params = jax.eval_shape(
        lambda key: serving_params(mod.init_params(key, mcfg), mcfg.dtype,
                                   mod.WIDE_PARAMS, mod.ROW_TABLES),
        jax.random.key(0))
    runner = ModelRunner(ecfg, params=params)
    assert runner.params is params
    # Held twice on purpose: the token table, 3,115,843,200 B of weights
    # and 50,257 x 1,664 x 2 = 167,255,296 B more.  The device holds a
    # 1,600-wide table in column order, which the head's matmul reads in
    # place and the embedding's gather cannot: one leaf for each use
    # costs 1% of the chip and saves a 161 MB copy in every step (PR 41).
    assert runner.param_bytes == 3_283_098_496
    assert params["wte_rows"].shape == (50257, 1664)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    weights = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), params)
    held = {"kv": on_chip(device_shape(
        ecfg.num_blocks, mcfg.n_layer, ecfg.block_size, mcfg.n_head,
        mcfg.head_dim), jnp.float32)}
    return runner, ecfg, held, weights, on_chip


def _assert_each_use_of_the_token_table_reads_its_own_leaf_in_place(text):
    """No ``copy`` makes a ``bf16[50257,1600]`` (handed ``wte`` alone the
    program laid all 161 MB of it out in row order for the gather of a
    step's rows, ``copy.27`` of the chip's trace); the gather reads the
    lane-padded leaf and the head's matmul ``wte``, each as the device
    holds it (row order, column order), with no operation between."""
    assert not re.search(r"= bf16\[50257,16\d\d\]\S* copy\(", text)
    entry = text[text.index("ENTRY "):]
    assert re.search(r"params__wte_rows__\S* = bf16\[50257,1664\]\{1,0:",
                     entry)
    assert re.search(r"params__wte__\S* = bf16\[50257,1600\]\{0,1:", entry)
    gather = [line for line in entry.splitlines()
              if "(%params__wte_rows__" in line]
    head = [line for line in entry.splitlines() if "(%params__wte__" in line]
    assert len(gather) == 1 and "/embed/gather" in gather[0], gather
    assert len(head) == 1 and "/lm_head/" in head[0] \
        and "dot_general" in head[0], head


def test_serving_cell_decode_program_fits_and_gathers_nothing(
        xl_runner, monkeypatch):
    """The XL cell's whole decode step (48 layers, the weights as the
    runner prepares them, 128 blocks, one bucket of 8), as
    ``ModelRunner`` jits it: one Mosaic kernel in the layer scan that
    takes the pool whole, no gather of every slot's whole table (8 x 64
    columns = 512 blocks of 16 x 25 x 64), no operation over the pool
    but the in-place update of the step's rows and none over a layer of
    it, no convert of a stacked weight (handed float32 weights the
    program cast all 48 layers in every run and held a 3.1 GB bf16 copy:
    10.73e9 bytes), no copy of the token table, and arguments, result
    and temporaries together in 4.64e9 of the chip's 16.9e9 bytes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = xl_runner
    bucket, i32 = ecfg.decode_batch_buckets[-1], jnp.int32
    pool = held["kv"]
    assert pool.shape == (48, 2, 128, 16, 1664)
    compiled = runner._decode.lower(
        held, weights,
        on_chip((bucket,), i32), on_chip((bucket,), i32),
        on_chip((bucket, ecfg.max_blocks_per_seq), i32),
        on_chip((bucket,), i32), on_chip((), i32),
        on_chip((bucket,), i32), on_chip((bucket,), i32)).compile()
    text = compiled.as_text()
    _assert_a_rows_token_may_come_from_the_last_steps_ids(text, bucket)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "paged_decode" in text
    assert "[512,16,25,64]" not in text and "[512,16,1664]" not in text
    _assert_the_pool_is_read_in_place_and_written_by_rows(
        text, pool.shape, lanes_used=25 * 64)
    assert not re.search(r"= bf16\[48,\d+,[\d,]+\]\S* convert\(", text)
    _assert_each_use_of_the_token_table_reads_its_own_leaf_in_place(text)
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= 1.30e9        # the pool, donated
    # no second pool, and since PR 41 no second token table: 168,024,576 B
    # of temporaries with ``wte`` alone, none with a leaf for each use
    assert mem.temp_size_in_bytes < 0.01e9
    assert held < 5e9, held


@pytest.mark.parametrize("bucket", [128, 512])
def test_serving_cell_prefill_program_copies_no_token_table(
        xl_runner, monkeypatch, bucket):
    """The XL cell's prefill at the bucket most of its prompts take and at
    its largest: the same ``_embed``, so the same two uses of the token
    table, each of its own leaf in place (the program made the decode
    step's 161 MB copy once a prompt too), and its temporaries fall by the
    table's bytes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, _, weights, on_chip = xl_runner
    assert bucket in ecfg.prefill_len_buckets
    compiled = runner._prefill.lower(
        None, weights, on_chip((1, bucket), jnp.int32),
        on_chip((), jnp.int32)).compile()
    _assert_each_use_of_the_token_table_reads_its_own_leaf_in_place(
        compiled.as_text())
    # 167,605,248 B with ``wte`` alone, at either bucket; 0.3e6 now
    assert compiled.memory_analysis().temp_size_in_bytes < 0.01e9


# ------------------------------------------- the Falcon-H1 cell's programs
@pytest.fixture(scope="module")
def falcon_h1_runner(v5e):
    """The cell's runner over abstract weights, and what its holder holds
    (the K/V pool and the store of recurrent state) as shapes on the chip."""
    import json
    from pathlib import Path

    from ray_tpu.models import falcon_h1
    from ray_tpu.serve.llm import EngineConfig
    from ray_tpu.serve.llm.config import resolve_model
    from ray_tpu.serve.llm.kv_cache import device_shape
    from ray_tpu.serve.llm.model_runner import ModelRunner
    engine = json.loads((Path(__file__).parent.parent / "perfbench" /
                         "configs" / "falcon-h1-34b.json").read_text()
                        )["serve"]["engine"]
    for key in ("decode_batch_buckets", "prefill_len_buckets"):
        engine[key] = tuple(engine[key])
    ecfg = EngineConfig(**engine)
    mod, mcfg = resolve_model(ecfg)
    assert mod is falcon_h1

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda key: mod.init_params(key, mcfg),
                            jax.random.key(0))
    runner = ModelRunner(ecfg, params=params)
    assert runner.params is params      # drawn in its serving type
    held = {
        "kv": on_chip(device_shape(ecfg.num_blocks, mcfg.n_layer,
                                   ecfg.block_size, mcfg.n_kv_head,
                                   mcfg.head_dim), jnp.float32),
        "state": {name: on_chip((mcfg.n_layer, ecfg.max_num_seqs + 1)
                                + s.shape, s.dtype)
                  for name, s in runner.state_spec.items()}}
    weights = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), params)
    return runner, ecfg, held, weights, on_chip


def _held_bytes(compiled):
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes, mem


def test_falcon_h1_decode_program_fits_and_steps_the_store_in_place(
        falcon_h1_runner, monkeypatch):
    """The cell's decode step at its one bucket of 32 (6 layers at the
    published widths, 1,024 blocks, 33 rows of state): the paged kernel
    at 20 / 4 heads x 128 in the layer scan, reading the pool whole (4 x
    128 = 512 lanes, whole tiles: no padding); K/V pool and store donated
    (1.25e9 bytes aliased), the pool touched by the update of the step's
    32 rows alone and no layer of it by anything; the scan's states
    stepped by ONE kernel a layer where they lie (``ssm_step_fusion``, the
    store its first result) and by no XLA fusion: neither the pass that
    wrote a layer's 33 rows back nor the one that read them again for ``y``
    (PR 72); no second copy of the store among the temporaries, and
    everything in 11.9e9 of the chip's 16.9e9 bytes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = falcon_h1_runner
    bucket, i32 = ecfg.decode_batch_buckets[-1], jnp.int32
    assert runner.param_bytes == 10_509_371_648
    compiled = runner._decode.lower(
        held, weights, on_chip((bucket,), i32), on_chip((bucket,), i32),
        on_chip((bucket, ecfg.max_blocks_per_seq), i32),
        on_chip((bucket,), i32), on_chip((), i32),
        on_chip((bucket,), i32), on_chip((bucket,), i32),
        on_chip((bucket,), i32)).compile()
    text = compiled.as_text()
    _assert_a_rows_token_may_come_from_the_last_steps_ids(text, bucket)
    kernels = re.findall(r'custom_call_target="tpu_custom_call".*'
                         r'op_name="[^"]*/(\w+)/pallas_call"', text)
    assert sorted(kernels) == ["paged_decode", "ssm_step_fusion"]
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # the kernel as the trace will name it: after the jitted function it
    # stands in, the aliased store its first result
    assert re.search(r"%ssm_step_fusion[.\d]* = \(f32\[6,33,32,128,256\]",
                     text)
    fusions = re.findall(r"= \(?(f32\[[\d,]+\])\S* fusion\(", text)
    assert fusions and "f32[6,33,3,5120]" in fusions      # the conv's tails
    assert "f32[6,33,32,128,256]" not in fusions
    assert "f32[33,32,128]" not in fusions
    assert held["kv"].shape == (6, 2, 1024, 16, 512)
    _assert_the_pool_is_read_in_place_and_written_by_rows(
        text, held["kv"].shape, lanes_used=4 * 128)
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 1.24e9
    assert mem.temp_size_in_bytes < 0.2e9       # the store is 0.84e9
    assert total < 12.5e9 < 16.9e9, total


def test_falcon_h1_prefill_program_fits_at_bucket_512(falcon_h1_runner,
                                                      monkeypatch):
    """The prefill program at the traffic's largest bucket: flash
    attention at 20 heads x 128 and the chunked scan, the state written
    to the staging row of the donated store."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, _, held, weights, on_chip = falcon_h1_runner
    compiled = runner._prefill.lower(
        held, weights, on_chip((1, 512), jnp.int32),
        on_chip((), jnp.int32)).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 1.24e9
    assert total < 12.5e9 < 16.9e9, total


# ------------------------------------------------ the training cells' steps
def _train_program(cell_name, v5e):
    """A training cell's program as ``perfbench/jobs/train.py`` builds it,
    with its state and batch as shapes on the described chip."""
    from perfbench import manifest
    from ray_tpu.parallel import mesh as mesh_lib, spmd
    from ray_tpu.parallel.mesh import MeshConfig
    cell = manifest.load_cell(manifest.load_manifest(), cell_name)
    config, spec = cell["config_file"], cell["traffic_file"]
    fam = manifest.family(config["family"])
    mod, options = fam.module(), config["train"]
    cfg = fam.model_config(config, options["model_options"])
    mc = MeshConfig(**options["mesh"]).resolved(1)
    mesh = mesh_lib.build_mesh(mc, list(v5e.device_set))
    prog = spmd.build_train_program(
        loss_fn=lambda p, b: mod.loss_fn(p, b, cfg),
        init_params_fn=lambda rng: mod.init_params(rng, cfg),
        optimizer=spmd.default_optimizer(moments_dtype=jnp.dtype(
            options["optimizer"]["moments_dtype"])),
        mesh=mesh, mesh_config=mc)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e),
        jax.eval_shape(prog.jitted_init, jax.random.key(0)))
    batch = {k: jax.ShapeDtypeStruct((spec["batch"], spec["seq"]), jnp.int32,
                                     sharding=v5e)
             for k in ("inputs", "targets")}
    return prog, state, batch


def test_kanana_step_compiles_with_its_kernels_and_fits(v5e, monkeypatch):
    """The whole step of ``kanana-2-30b-a3b.train-b2-s8192`` (910.6 M
    parameters in bf16 with bf16 moments, 2 x 8,192 tokens): the flash
    kernels on latent attention's five operands (forward and backward once
    in each of the two layer scans, each known to ``mla.attention_ms`` by
    a first result ``bf16[64,8192,128]``: the output, dq_nope; 8,192
    positions need more than Mosaic's default scoped VMEM) and nothing 192
    wide anywhere in the step, every grouped matmul over the
    16 held experts a megablox kernel at a tile that divides 768 and 2,048
    (11 in the sparse scan: the forward's three, gate and up recomputed,
    six of the backward; no down projection is recomputed since the
    combine's gradient reads no output row), each with a result shape its
    metric is keyed on, no float32 copy of the sorted rows, no select,
    re-tiled copy or scatter among the sorted passes, no ``ragged-dot``
    fallback, and everything inside the chip.  The layer holds 16 of 128
    experts, so its five row passes are the kernels whose work list ends
    at ``held_rows`` (PR 56): ``spread_held_rows`` three times (forward,
    recomputed, the combine's backward) and ``sum_held_slots`` twice, under
    names no reducer of the experts', the attention's or the
    ``tpu_custom_call`` metrics matches, though a spread's result has the
    sorted rows' shape; no gather over the 98,304 rows is left, and the
    step's temporaries are its parent's 9,365,980,160 B (e45e91f, this
    jax) to two megabytes: 1,082,880 B more, the sum's list by token (two
    arrays of 393,216 B) and what the schedule made of it; the (6, 16,384,
    2,048) rows gathered back, 403 MB, are gone but were never the peak.
    Since PR 66 the kernels read a sparse layer's 16 held experts in their
    stack of 7 layers, ``bf16[112,...]``, in place: no instruction but the
    three ``tgmm`` results and their way into the gradients' stack has a
    layer's expert matrix as its result (the parent: three sliced copies
    in each loop, and a second copy of five of them to another memory),
    and the temporaries read 9,265,399,808 B under the bound kept here.
    Since PR 68 a sparse layer's router picks its 6 of 128 experts in the
    kernel ``router_choice``, once in each loop (9,240,136,704 B)."""
    import json
    from pathlib import Path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prog, state, batch = _train_program("kanana-2-30b-a3b.train-b2-s8192",
                                        v5e)
    compiled = prog.jitted_step.lower(state, batch).compile()
    text = compiled.as_text()
    kernels = _kernel_names_and_results(text)
    metrics = Path(__file__).parent.parent / "perfbench" / "layer_metrics"
    keyed = {name: json.loads((metrics / f"{name}.json").read_text())
             ["params"] for name in ("mla.attention_ms",
                                     "moe.held_expert_ms")}

    def read_by(metric):
        """The kernels ``kernel_ms_per_step`` counts under ``metric``: a
        name that holds one of its substrings, a result of its shapes."""
        return [shape for name, shape in kernels
                if shape in keyed[metric]["shapes"]
                and any(part in name for part in keyed[metric]["names"])]
    flash, experts = read_by("mla.attention_ms"), read_by("moe.held_expert_ms")
    assert flash == ["bf16[64,8192,128]"] * 4, kernels
    for joined in ("[2,8192,32,192]", "[2,32,8192,192]", "[64,8192,192]"):
        assert joined not in text
    assert len(experts) == 11
    assert set(experts) == set(keyed["moe.held_expert_ms"]["shapes"])
    read = keyed["mla.attention_ms"]["names"] \
        + keyed["moe.held_expert_ms"]["names"]
    walks = sorted((name.split(".")[0], shape) for name, shape in kernels
                   if not any(part in name for part in read))
    assert walks == [("router_choice", "s32[8,16384]")] * 2 \
        + [("spread_held_rows", "bf16[98304,2048]")] * 3 \
        + [("sum_held_slots", "bf16[16384,2048]")] * 2, kernels
    _assert_the_router_picks_in_vmem(text, 16384, 128, calls=2)
    assert len(flash) + len(experts) + len(walks) == len(kernels)
    assert "ragged-dot" not in text
    assert not re.search(r"\[16384,128,\d+\]", text)    # no dispatch tensor
    # the combine makes no float32 copy of the sorted rows, forward or back
    assert "f32[16384,6,2048]" not in text
    assert not re.search(r"= f32\[98304,2048\].*moe_combine", text)
    _assert_the_sorted_passes_are_only_the_layers_work(
        text, "bf16[98304,2048]", "[16384,6,2048]")
    assert not re.search(r"= bf16\[98304,2048\]\S* gather\(", text)
    _assert_no_layers_experts_are_made(
        text, ("bf16[16,2048,768]", "bf16[16,768,2048]"))
    assert "bf16[7,112," not in text and "bf16[7,16,112," not in text
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= 5.46e9        # the state, donated
    assert mem.temp_size_in_bytes <= 9_365_980_160 + 2 * 2 ** 20, \
        mem.temp_size_in_bytes
    assert held < 16.9e9, held


def _operations_and_kernels(lowered_text):
    """What a lowered step computes, without how it is nested or where it
    was written: the count of each StableHLO operation, and each Mosaic
    kernel as (its MLIR printed without locations, its operand and result
    types).  A kernel's payload holds source lines, and a private
    function's name its number in the module: neither is the program."""
    import base64
    import collections
    import hashlib
    import json

    from jax._src.lib.mlir import ir
    ops = collections.Counter(
        re.findall(r"= \"?((?:stablehlo|chlo)\.[a-z_]+)", lowered_text))
    kernels = collections.Counter()
    for line in lowered_text.splitlines():
        if "@tpu_custom_call" not in line:
            continue
        raw = re.search(r'backend_config = "((?:[^"\\]|\\.)*)"', line).group(1)
        config = json.loads(re.sub(r"\\([0-9A-Fa-f]{2})",
                                   lambda m: chr(int(m.group(1), 16)), raw))
        body = base64.b64decode(config["custom_call_config"]["body"])
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(body).operation.get_asm(
                enable_debug_info=False)
        kernels[(hashlib.sha256(asm.encode()).hexdigest(),
                 line.rsplit(" : ", 1)[1])] += 1
    summary = json.dumps([sorted(ops.items()),
                          sorted((k[0], k[1], n) for k, n in kernels.items())])
    return hashlib.sha256(summary.encode()).hexdigest()[:16], ops, kernels


# The digests of the two older training cells' steps as the parent of the PR
# that taught the expert layer its share lowered them (commit 19ffdeb, this
# jax).  OLMoE: 4,092 operations and 10 Mosaic kernels (flash forward,
# twice, and backward; three gmm at (512, 1024, 1024), their transposes and
# tgmm); GPT-2 XL: 1,851 and the two flash kernels.  ops/moe.py, the flash
# kernel's widths and VMEM rule and the optimizer's mask changed around
# them; what these steps compute did not.  A PR that changes such a step on
# purpose replaces its digest: PR 45 did OLMoE's (``dropless_experts``: the
# router's weight multiplies the hidden rows in float32 and the combine is
# the dispatch transposed, so the checkpointed layer's backward calls no
# down projection a second time: 4,039 operations and 9 kernel calls where
# there were 4,092 and 10); PR 49 did it again (the one sort carries the
# weights and is sorted back for the inverse and for the weights' gradient,
# the gathers promise their indices, the k slots lead the rows gathered
# back, the counts are a compare and a sum: no ``select``, ``clamp`` or
# ``scatter`` of ``jnp.take`` and ``bincount``, 3,905 operations).  PR 50
# replaced both: the flash backward's Mosaic body is in them, and its tile
# is computed keys-down now (``k . q^T``, lse and delta read as the lane
# vectors they are stored as, dv and dk plain products, dq's the one
# contracted over dimension 0: no ``vector.transpose`` of lse or delta in
# the body).  The kernel's operands, results, grid and name are what they
# were, and so are the counts: 3,905 operations and 9 kernels, 1,851 and 2.
# PR 53 replaced GPT-2 XL's on purpose: its block hands the fused projection
# to ``flash_fwd_pairs`` / ``flash_bwd_pairs`` with the heads unsplit
# (``"bte,eck->bctk"``, two 64-wide heads a 128-lane block, delta made in
# the backward kernel), so the slices, reshapes, transposes, sharding
# constraints and the delta pass round the old kernels are gone: 1,795
# operations where there were 1,851, still 2 kernels, other bodies.  OLMoE's
# stood: ``flash_attention`` lowers to what it lowered to.  PR 66 replaced
# OLMoE's on purpose (479998fc66d84fe8, 3,905 operations until then): the
# layer scan's body closes over the three expert leaves whole,
# ``(3 x 64, ...)``, takes the layer's index among its ``xs``, and the six
# ``gmm`` calls a layer that read the weights read the stack under the
# layer's counts laid among its 192 groups (``ops/moe._megablox_at``, one
# ``dynamic_update_slice`` of 64 counts a layer); what LEFT is every use of
# the scan's own slice of the experts, which now only names where the
# ``tgmm`` results go: 4,089 operations, the same 9 kernels with the same
# bodies, the ``gmm`` ones over ``192`` groups where they had 64.  PR 68
# replaced OLMoE's on purpose (0d4c147702843346, 4,089 operations until
# then): the router's ``lax.top_k`` of 8 in 64 stands inside
# ``ops/moe.choose_experts``' ``custom_vjp`` (64 experts are no whole lane
# block, so the forward is the parent's), whose backward is 8 compares,
# selects and adds over (8192, 64); what LEFT is a ``scatter``, ``top_k``'s
# own derivative into the zeroed scores (38 -> 37): 4,139 operations, the
# same 9 kernels.  XL's has no router and stood.
PARENT_STEPS = {
    "olmoe-1b-7b.train-b2-s4096": ("b4a26fbc3b10a6a5", 4139, 9),
    "gpt2-xl-1558m.train-b8-s1024": ("bcfab170aaa276dc", 1795, 2),
}


@pytest.mark.parametrize("cell", sorted(PARENT_STEPS))
def test_older_training_steps_lower_to_the_operations_and_kernels_they_had(
        v5e, monkeypatch, cell):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prog, state, batch = _train_program(cell, v5e)
    digest, ops, kernels = _operations_and_kernels(
        prog.jitted_step.lower(state, batch).as_text())
    assert (digest, sum(ops.values()), sum(kernels.values())) == \
        PARENT_STEPS[cell]
    # PR 56's kernels are for a layer that holds a share of its experts:
    # OLMoE holds all 64 and XL has none; XL's digest is e45e91f's still
    assert digest in ("b4a26fbc3b10a6a5", "bcfab170aaa276dc")


def test_xl_step_holds_no_split_head_and_no_copy_round_its_kernels(
        v5e, monkeypatch):
    """The whole step of ``gpt2-xl-1558m.train-b8-s1024`` (48 layers, 25
    heads of 64, 8 x 1,024 tokens, ``remat_policy`` ``attn``): the fused
    projection goes to ``flash_fwd_pairs`` / ``flash_bwd_pairs`` as it
    stands, so no array anywhere in the step has a 64-wide minor dimension
    (the parent held ``bf16[8,1024,25,64]`` q, k, v, dO, dq, dk, dv and a
    kept ``bf16[200,1024,64]`` output, half of every lane tile padding), no
    ``copy`` stands under ``attn`` or ``attn_qkv`` in either layer scan
    (the parent: nine a layer) and delta is made in the backward kernel;
    one forward and one backward kernel, each under its own name; and the
    temporaries are no more than the parent's 9,499,432,448 B (599491d,
    this jax).  What the described compile holds in all, 18.8e9 B with the
    donated state counted twice over, is not asserted: the parent reads
    18.96e9 there and the cell runs (PERF.md section 7)."""
    from ray_tpu.util import tracing
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # as the programs run: the op map reads one frame a location
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    prog, state, batch = _train_program("gpt2-xl-1558m.train-b8-s1024", v5e)
    compiled = prog.jitted_step.lower(state, batch).compile()
    text = compiled.as_text()
    for split in ("[8,1024,25,64]", "[8,25,1024,64]", "[200,1024,64]"):
        assert split not in text
    ops = tracing.op_map(text)
    under = {name: e for name, e in ops.items()
             if e["scope"].split("/")[0] in ("attn", "attn_qkv")}
    assert len(under) > 20, len(under)
    assert not [name for name in under if name.startswith("copy.")]
    kernels = sorted((e["scope"], e["pass"]) for name, e in ops.items()
                     if name.startswith("tpu_custom_call"))
    assert kernels == [("attn/flash_bwd_pairs", "bwd"),
                       ("attn/flash_fwd_pairs", "fwd")]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 9.46e9        # the state, donated
    assert mem.temp_size_in_bytes <= 9_499_432_448, mem.temp_size_in_bytes


def test_olmoe_step_holds_no_more_temporaries_than_its_parent(v5e,
                                                              monkeypatch):
    """The whole step of ``olmoe-1b-7b.train-b2-s4096`` stood at 95.6% of
    the chip (8.79e9 B of state, donated, and 7.36e9 of temporaries), so a
    change to the expert layer may add no array to it.  Until PR 66 the
    bound was the 7,358,946,304 B of PR 49's parent (5278554, this jax),
    whose ``jnp.take`` filled, re-tiled ``bf16[8192,8,2048]`` and counted
    by scatter-add; PR 66's parent read 7,358,591,488.  Since PR 66 the
    kernels read a layer's 64 experts in their stack of 3 layers,
    ``bf16[192,...]`` (a bitcast of the parameter, one constant of both
    loops), in place: no instruction but the three ``tgmm`` results has a
    layer's expert matrix as its result (the parent: a
    ``dynamic-slice_bitcast_fusion`` each, three in each loop, 805 MB a
    loop and layer read and written), no second array of the stack's size
    is made for it, and the temporaries are 6,753,362,432 B, 605 MB less,
    to two megabytes; and the sorted passes of the whole step are the
    layer's work alone."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prog, state, batch = _train_program("olmoe-1b-7b.train-b2-s4096", v5e)
    compiled = prog.jitted_step.lower(state, batch).compile()
    text = compiled.as_text()
    _assert_the_sorted_passes_are_only_the_layers_work(
        text, "bf16[65536,2048]", "[8192,8,2048]")
    _assert_no_layers_experts_are_made(
        text, ("bf16[64,2048,1024]", "bf16[64,1024,2048]"))
    # the stack the kernels read is the parameter itself, seen whole
    whole = re.findall(r"= bf16\[192,(?:2048,1024|1024,2048)\]\S* ([\w-]+)\(",
                       text)
    assert whole and set(whole) <= {"bitcast", "get-tuple-element",
                                    "parameter"}, set(whole)
    assert "bf16[3,192," not in text
    # the gradients' stacks are zeroed once and written a layer at a time,
    # as they were: nothing else of the backward has the stack's size
    backward = re.findall(
        r"%(\S+) = bf16\[3,64,(?:2048,1024|1024,2048)\]\S* "
        r"(?:add|copy|fusion)\(", text)
    assert sorted(name.split(".")[0] for name in backward if not
                  name.startswith("fusion")) == \
        ["bitcast_dynamic-update-slice_fusion"] * 3, backward
    held, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 8.78e9        # the state, donated
    assert mem.temp_size_in_bytes <= 6_753_362_432 + 2 * 2 ** 20, \
        mem.temp_size_in_bytes
    assert held < 16.9e9, held


def test_qwen3_next_step_reads_its_experts_in_their_stacks(v5e, monkeypatch):
    """The whole step of ``qwen3-next-80b-a3b.train-b2-s8192`` (one period:
    three DeltaNet layers in the inner scan, one attention layer): since
    PR 66 the kernels read a DeltaNet layer's 64 held experts in their
    stack of 3 layers, ``bf16[192,...]``, in place, so no instruction but
    the ``tgmm`` results has a layer's expert matrix as its result (the
    parent: a ``dynamic-slice_bitcast_fusion`` each, three in each loop of
    the inner scan); the attention layer's stack is one layer, a
    ``bitcast`` of its parameter on both sides, which the optimizer's
    fusions ``convert`` into.  Every grouped matmul keeps the result its
    share's metric knows it by.  Since PR 70 the rule takes the conv's
    output whole: in the step, under the block's checkpoint, the solve
    stands once, the kernel that follows twice (the pass and its
    recomputation) and each backward once, XLA makes no float32 array
    the size of q and no slice the size of v under ``gdn_rule``, and the
    temporaries are 8,819,227,648 B (PR 68's step: 9,359,284,736; PR
    66's 9,424,205,824) to two megabytes.  ~70 s."""
    import json
    from pathlib import Path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prog, state, batch = _train_program("qwen3-next-80b-a3b.train-b2-s8192",
                                        v5e)
    compiled = prog.jitted_step.lower(state, batch).compile()
    text = compiled.as_text()
    _assert_no_layers_experts_are_made(
        text, ("bf16[64,2048,512]", "bf16[64,512,2048]"),
        also=("bitcast", "convert"))
    assert "bf16[3,192," not in text
    keyed = json.loads((Path(__file__).parent.parent / "perfbench"
                        / "layer_metrics"
                        / "moe.train_share_expert_peak_share.json"
                        ).read_text())["params"]
    experts = [shape for name, shape in _kernel_names_and_results(text)
               if shape in keyed["shapes"]
               and any(part in name for part in keyed["names"])]
    # 11 a layer as in Kanana's step, written once for the inner scan's
    # two loops and once for the attention layer
    assert len(experts) == 2 * 11
    assert set(experts) == set(keyed["shapes"])
    assert "ragged-dot" not in text
    # 10 of 512: once in each loop of the inner scan, twice for the
    # attention layer (PR 68; the temporaries read 9,359,284,736 B)
    _assert_the_router_picks_in_vmem(text, 16384, 512, calls=4)
    assert sorted(name.split(".")[0] for name, _
                  in _kernel_names_and_results(text) if "delta_rule" in name
                  ) == ["delta_rule_bwd", "delta_rule_fwd", "delta_rule_fwd",
                        "delta_rule_solve", "delta_rule_solve_bwd"]
    under_rule = _under_scope(text, "gdn_rule")
    assert len(under_rule) > 100
    assert not [name for name, shape in under_rule
                if shape == "f32[2,8192,2048]"
                or name.startswith("slice") and shape == "bf16[2,8192,4096]"]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= 8_819_227_648 + 2 * 2 ** 20, \
        mem.temp_size_in_bytes


# --------------------------------------------- the LFM2-MoE cell's programs
@pytest.fixture(scope="module")
def lfm2_runner(v5e):
    """The cell's runner over abstract weights, and what its holder holds
    as shapes on the chip: a K/V pool over the 2 attention layers and a
    store of conv tails over the 7 conv layers."""
    import json
    from pathlib import Path

    from ray_tpu.models import lfm2
    from ray_tpu.serve.llm import EngineConfig
    from ray_tpu.serve.llm.config import resolve_model
    from ray_tpu.serve.llm.kv_cache import device_shape
    from ray_tpu.serve.llm.model_runner import ModelRunner
    engine = json.loads((Path(__file__).parent.parent / "perfbench" /
                         "configs" / "lfm2-24b-a2b.json").read_text()
                        )["serve"]["engine"]
    for key in ("decode_batch_buckets", "prefill_len_buckets"):
        engine[key] = tuple(engine[key])
    ecfg = EngineConfig(**engine)
    mod, mcfg = resolve_model(ecfg)
    assert mod is lfm2

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda key: mod.init_params(key, mcfg),
                            jax.random.key(0))
    runner = ModelRunner(ecfg, params=params)
    assert runner.params is params      # drawn in its serving type
    assert (runner.kv_layers, runner.state_layers) == (2, 7)
    held = {
        "kv": on_chip(device_shape(ecfg.num_blocks, runner.kv_layers,
                                   ecfg.block_size, mcfg.n_kv_head,
                                   mcfg.head_dim), jnp.float32),
        "state": {name: on_chip((runner.state_layers, ecfg.max_num_seqs + 1)
                                + s.shape, s.dtype)
                  for name, s in runner.state_spec.items()}}
    weights = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), params)
    return runner, ecfg, held, weights, on_chip


# the float32 reference widens a routed layer's experts beside the engine
# it checks: 64 x 3 x 2,048 x 1,536 x 4 bytes and the layer's other leaves
LFM2_REFERENCE_LAYER_BYTES = 2.48e9


def test_lfm2_decode_program_reads_the_experts_in_place_and_fits(
        lfm2_runner, monkeypatch):
    """The cell's decode step at its one bucket of 32 (9 layers at the
    published widths, 2,048 blocks, 33 rows of conv tails): the paged
    kernel at 32 / 8 heads x 64 once in the period's body, reading the
    2-layer pool whole (8 x 64 = 512 lanes: no padding); 128 assignments
    are no multiple of megablox's row tile, so the experts' 24 grouped
    matmuls are XLA's ragged-dot kernels, each over the WHOLE stack of a
    period position's experts (2 x 64 groups, the other period's empty):
    nothing the size of a layer's experts is made (sliced out of the
    stack by the scan, each of 12 would be a 0.4 GB copy: 0.41e9 bytes of
    temporaries, seen before the experts were kept out of the scan);
    pool and store donated, the pool touched by the step's 32 rows alone;
    the ids come back as (8, 32, 4); and everything fits the chip with
    the check's float32 layer beside it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = lfm2_runner
    bucket, i32 = ecfg.decode_batch_buckets[-1], jnp.int32
    assert runner.param_bytes == 10_355_981_312
    lowered = runner._decode.lower(
        held, weights, on_chip((bucket,), i32), on_chip((bucket,), i32),
        on_chip((bucket, ecfg.max_blocks_per_seq), i32),
        on_chip((bucket,), i32), on_chip((), i32),
        on_chip((bucket,), i32), on_chip((bucket,), i32),
        on_chip((bucket,), i32))
    ids = jax.tree.leaves(lowered.out_info)[-1]
    assert ids.shape == (8, 32, 4) and ids.dtype == jnp.int32
    compiled = lowered.compile()
    text = compiled.as_text()
    # the ids go out with the count of touched experts behind them (33),
    # and again at the bucket's width for the step enqueued behind this one
    root = next(line for line in text[text.index("ENTRY "):].splitlines()
                if " ROOT " in line)
    assert "s32[33]" in root and "s32[32]" in root and "s32[8,32,4]" in root
    assert "paged_decode" in text
    assert len(re.findall(r"= bf16\[128,(?:1536|2048)\]\S* custom-call\(.*"
                          r"ragged_dot_tiling", text)) == 12
    assert not re.search(r"= bf16\[64,(2048,1536|1536,2048)\]", text)
    assert held["kv"].shape == (2, 2, 2048, 16, 512)
    assert held["state"]["conv"].shape == (7, 33, 2, 2048)
    _assert_the_pool_is_read_in_place_and_written_by_rows(
        text, held["kv"].shape, lanes_used=8 * 64)
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 0.27e9
    assert mem.temp_size_in_bytes < 0.05e9
    assert total + LFM2_REFERENCE_LAYER_BYTES < 16.9e9, total


def test_lfm2_prefill_program_fits_at_bucket_512(lfm2_runner, monkeypatch):
    """The prefill program at the traffic's largest bucket: flash
    attention at 32 heads x 64, 2,048 assignments through megablox (12
    kernels over the whole stacks), the conv tails written to the staging
    row of the donated store, the ids (8, 512, 4)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, _, held, weights, on_chip = lfm2_runner
    lowered = runner._prefill.lower(
        held, weights, on_chip((1, 512), jnp.int32), on_chip((), jnp.int32))
    ids = jax.tree.leaves(lowered.out_info)[-1]
    assert ids.shape == (8, 512, 4) and ids.dtype == jnp.int32
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(re.findall(r"%gmm[.\d]* = bf16\[2048,(?:1536|2048)\]",
                          text)) == 12
    assert "ragged_dot_tiling" not in text
    assert not re.search(r"= bf16\[64,(2048,1536|1536,2048)\]", text)
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 0.27e9
    assert total + LFM2_REFERENCE_LAYER_BYTES < 16.9e9, total


# ------------------------------------------- the MiniCPM-SALA cell's programs
@pytest.fixture(scope="module")
def minicpm_sala_runner(v5e):
    """The cell's runner over abstract weights, and what its holder holds
    as shapes on the chip: a K/V pool over the 4 sparse layers, the
    selector's cache beside it (a half-kernel every 16 positions of every
    page) and a store of Lightning states over the 12 Lightning layers."""
    import json
    from pathlib import Path

    from ray_tpu.models import minicpm_sala
    from ray_tpu.serve.llm import EngineConfig
    from ray_tpu.serve.llm.config import resolve_model
    from ray_tpu.serve.llm.kv_cache import device_shape, selector_shape
    from ray_tpu.serve.llm.model_runner import ModelRunner
    engine = json.loads((Path(__file__).parent.parent / "perfbench" /
                         "configs" / "minicpm-sala-9b.json").read_text()
                        )["serve"]["engine"]
    for key in ("decode_batch_buckets", "prefill_len_buckets"):
        engine[key] = tuple(engine[key])
    ecfg = EngineConfig(**engine)
    mod, mcfg = resolve_model(ecfg)
    assert mod is minicpm_sala

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda key: mod.init_params(key, mcfg),
                            jax.random.key(0))
    runner = ModelRunner(ecfg, params=params)
    assert runner.params is params      # drawn in its serving type
    assert (runner.kv_layers, runner.state_layers) == (4, 12)
    assert runner.select_spec == {"stride": 16, "block": 64}
    assert runner.chunk == 2048
    pool = device_shape(ecfg.num_blocks, runner.kv_layers, ecfg.block_size,
                        mcfg.n_kv_head, mcfg.head_dim)
    held = {
        "kv": on_chip(pool, jnp.float32),
        "state": {name: on_chip((runner.state_layers, ecfg.max_num_seqs + 1)
                                + s.shape, s.dtype)
                  for name, s in runner.state_spec.items()},
        "sel": on_chip(selector_shape(pool, runner.select_spec["stride"]),
                       jnp.float32)}
    weights = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), params)
    return runner, ecfg, held, weights, on_chip


# the float32 reference beside the engine: a feed-forward matrix widened
# (4,096 x 16,384 x 4 bytes, three of them in one jitted layer) and a block
# of 256 queries' scores over the check's 12,288 positions
MINICPM_SALA_REFERENCE_BYTES = 1.3e9


def test_minicpm_sala_decode_program_walks_the_chosen_pages_and_fits(
        minicpm_sala_runner, monkeypatch):
    """The cell's decode step at its one bucket of 4 (16 layers at the
    published widths, 4,224 pages of 64 positions, 5 rows of state): the
    paged kernel in its listed form once a sparse layer (4 custom calls),
    the pool (2.21e9 bytes), the store and the selector's cache donated,
    the pool touched by the update of the step's 4 rows alone, no second
    array of the pool's size, the two counts of pages behind the ids, and
    everything in the chip with the check's reference beside it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = minicpm_sala_runner
    bucket, i32 = ecfg.decode_batch_buckets[-1], jnp.int32
    assert runner.param_bytes == 10_079_082_496
    assert held["kv"].shape == (4, 2, 4224, 64, 256)
    assert held["sel"].shape == (4, 4224, 4, 256)
    assert held["state"]["s"].shape == (12, 5, 32, 128, 128)
    compiled = runner._decode.lower(
        held, weights, on_chip((bucket,), i32), on_chip((bucket,), i32),
        on_chip((bucket, ecfg.max_blocks_per_seq), i32),
        on_chip((bucket,), i32), on_chip((), i32),
        on_chip((bucket,), i32), on_chip((bucket,), i32),
        on_chip((bucket,), i32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert "paged_decode_listed" in text
    # the ids with the pages read and the pages held behind them (6), and
    # again at the bucket's width for the step enqueued behind this one
    root = next(line for line in text[text.index("ENTRY "):].splitlines()
                if " ROOT " in line)
    assert "s32[6]" in root and "s32[4]" in root
    _assert_the_pool_is_read_in_place_and_written_by_rows(
        text, held["kv"].shape, lanes_used=2 * 128)
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 2.40e9    # pool, store and selector
    assert mem.temp_size_in_bytes < 0.15e9
    assert total + MINICPM_SALA_REFERENCE_BYTES < 16.9e9, total


def test_minicpm_sala_chunk_program_is_one_and_keeps_the_pool_out(
        minicpm_sala_runner, monkeypatch):
    """The one prefill program: a chunk of 2,048 positions over a staging
    K/V of 67,584 positions (donated with the holder and returned: 0.57e9
    bytes with its half-kernels) and the store's staging row; the sparse
    layers' attention is the flash kernel over the staging (4 custom
    calls); the pool goes through untouched: nothing of its size is made,
    and the chunk's own temporaries are 0.95e9 bytes, of which the
    kernel's mask of positions (2 x 2,048 x 67,584, as int8 and as bool
    before it) is 0.55e9."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = minicpm_sala_runner
    staging = jax.tree.map(lambda s: on_chip(s.shape, s.dtype),
                           runner.staging_spec)
    assert staging["k"].shape == (4, 67584, 256)
    assert staging["halves"].shape == (4, 67584 // 16, 256)
    assert runner.staging_bytes == 2 * 4 * 67584 * 256 * 4 \
        + 4 * 4224 * 256 * 4
    i32 = jnp.int32
    compiled = runner._prefill_chunk.lower(
        held, weights, staging, on_chip((1, runner.chunk), i32),
        on_chip((), i32), on_chip((), i32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert "sparse_prefill" in text
    assert not _made(text, math.prod(held["kv"].shape))
    total, mem = _held_bytes(compiled)
    # holder and staging aliased to the result
    assert mem.alias_size_in_bytes >= 2.40e9 + 0.57e9
    assert mem.temp_size_in_bytes < 1.0e9
    assert total + MINICPM_SALA_REFERENCE_BYTES < 16.9e9, total


# The serving cells' decode steps as the parent of the PR that taught the
# paged kernel to walk a list of pages lowered them (commit 1a45b60, this
# jax): digest, StableHLO operations, Mosaic kernels.  The kernel's body
# builds either walk; the walk without a list is the one there was,
# operation for operation.  ``lfm2`` is the step's since PR 45 changed
# ``ops/moe.dropless_experts`` on purpose (the weights sorted with the rows
# and multiplied into the hidden rows in float32, no ``nkd,nk->nd`` product:
# 1,367 operations where there were 1,314), and since PR 49 did again (one
# sort of (expert id, iota, weight) and one of the order back, gathers that
# promise their indices, the assignments numbered slot by slot, the counts
# a compare and a sum: 1,372 operations, none of them a scatter-add).
# PR 68 put ``route_sigmoid``'s ``top_k`` and ``take_along_axis`` inside
# ``ops/moe.choose_experts``; a decode step's 32 rows of 64 experts take
# them as they did and no program differentiates them: all three stood.
# ``falcon_h1`` is the step's since PR 72 changed ``models/falcon_h1.py``'s
# ``forward_decode`` on purpose: the scan's states are stepped by the Pallas
# kernel ``ssm_step_fusion`` where they lie (``ops/ssm.ssm_step_rows``: one
# pass over the batch's 32 rows) and no longer taken out of the store,
# stepped all 33 in the store's order and put back: 641 operations where
# there were 673, two Mosaic kernels where there was ``paged_decode`` alone.
# The kernel's body and its block (``ssm.STEP_HEADS``, ``STEP_BLOCKS``,
# ``STEP_ROWS``) are in the digest.  ``xl`` and ``lfm2`` stand.
PARENT_DECODE_STEPS = {
    "xl": ("b5e4a17574d47c3c", 413, 1),
    "falcon_h1": ("a217ec7d503ee80b", 641, 2),
    "lfm2": ("92e664a01969a09a", 1372, 1),
}


@pytest.mark.parametrize("cell", sorted(PARENT_DECODE_STEPS))
def test_older_decode_steps_lower_to_the_operations_and_kernels_they_had(
        cell, request, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = request.getfixturevalue(
        f"{cell}_runner")
    bucket, i32 = ecfg.decode_batch_buckets[-1], jnp.int32
    # last_ids, src, and state_rows where the holder has a store
    rows = [on_chip((bucket,), i32)] * (2 + ("state" in held))
    lowered = runner._decode.lower(
        held, weights, on_chip((bucket,), i32), on_chip((bucket,), i32),
        on_chip((bucket, ecfg.max_blocks_per_seq), i32),
        on_chip((bucket,), i32), on_chip((), i32), *rows)
    digest, ops, kernels = _operations_and_kernels(lowered.as_text())
    assert (digest, sum(ops.values()), sum(kernels.values())) == \
        PARENT_DECODE_STEPS[cell]


# ------------------------------------------------ the Trinity cell's programs
@pytest.fixture(scope="module")
def afmoe_runner(v5e):
    """The cell's runner over abstract weights, and what its holder holds
    as shapes on the chip: pages of two kinds, a pool over the one full
    layer and a pool over the 4 window layers."""
    import json
    from pathlib import Path

    from ray_tpu.models import afmoe
    from ray_tpu.serve.llm import EngineConfig
    from ray_tpu.serve.llm.config import resolve_model
    from ray_tpu.serve.llm.kv_cache import device_shape, window_columns
    from ray_tpu.serve.llm.model_runner import ModelRunner
    engine = json.loads((Path(__file__).parent.parent / "perfbench" /
                         "configs" / "trinity-large-preview.json").read_text()
                        )["serve"]["engine"]
    for key in ("decode_batch_buckets", "prefill_len_buckets"):
        engine[key] = tuple(engine[key])
    ecfg = EngineConfig(**engine)
    mod, mcfg = resolve_model(ecfg)
    assert mod is afmoe

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda key: mod.init_params(key, mcfg),
                            jax.random.key(0))
    runner = ModelRunner(ecfg, params=params)
    assert runner.params is params      # drawn in its serving type
    assert (runner.kv_layers, runner.window_layers, runner.state_layers,
            runner.window, runner.chunk) == (1, 4, 0, 4096, 2048)
    assert runner.state_spec is None and runner.select_spec is None
    window_blocks = ecfg.max_num_seqs * window_columns(runner.window,
                                                       ecfg.block_size)
    held = {
        "kv": on_chip(device_shape(ecfg.num_blocks, 1, ecfg.block_size,
                                   mcfg.n_kv_head, mcfg.head_dim),
                      jnp.float32),
        "kvw": on_chip(device_shape(window_blocks, 4, ecfg.block_size,
                                    mcfg.n_kv_head, mcfg.head_dim),
                       jnp.float32)}
    weights = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), params)
    return runner, ecfg, held, weights, on_chip


# the float32 reference beside the engine: a block of 4 experts widened
# (3 x 4 x 3,072 x 3,072 x 4 bytes), their hidden rows at the check's 6,152
# positions, and a block of 256 queries' scores over them
AFMOE_REFERENCE_BYTES = 1.2e9


def test_afmoe_decode_program_walks_each_kind_of_page_and_fits(
        afmoe_runner, monkeypatch):
    """The cell's decode step at its one bucket of 16 (5 layers at the
    published widths): the paged kernel once a layer, under the window in
    the 4 sliding layers (``paged_decode_window``, over the window pool
    and the window tables) and over the whole context in the full one;
    both pools donated (2.15e9 + 2.18e9 bytes) and each touched by the
    update of the step's 16 rows alone; 64 assignments are no multiple of
    megablox's row tile, so the held experts' 12 grouped matmuls are XLA's
    ragged-dot kernels, each over a layer's own 32 experts (no layer scan,
    no slice of a stack); the ids come back as (4, 16, 4) with the count of
    held experts touched behind the step's 16 ids."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = afmoe_runner
    bucket, i32 = ecfg.decode_batch_buckets[-1], jnp.int32
    assert runner.param_bytes == 8_643_941_376
    tables = on_chip((bucket, ecfg.max_blocks_per_seq), i32)
    lowered = runner._decode.lower(
        held, weights, on_chip((bucket,), i32), on_chip((bucket,), i32),
        tables, on_chip((bucket,), i32), on_chip((), i32),
        on_chip((bucket,), i32), on_chip((bucket,), i32), tables)
    ids = jax.tree.leaves(lowered.out_info)[-1]
    assert ids.shape == (4, 16, 4) and ids.dtype == jnp.int32
    compiled = lowered.compile()
    text = compiled.as_text()
    root = next(line for line in text[text.index("ENTRY "):].splitlines()
                if " ROOT " in line)
    assert "s32[17]" in root and "s32[16]" in root and "s32[4,16,4]" in root
    kernels = re.findall(r'custom_call_target="tpu_custom_call".*'
                         r'op_name="[^"]*/(\w+)/pallas_call"', text)
    assert sorted(kernels) == ["paged_decode"] + ["paged_decode_window"] * 4
    assert len(re.findall(r"= bf16\[64,3072\]\S* custom-call\(.*"
                          r"ragged_dot_tiling", text)) == 12
    assert held["kv"].shape == (1, 2, 4096, 64, 1024)
    assert held["kvw"].shape == (4, 2, 1040, 64, 1024)
    # each pool is made once at its size: the row update, aliased
    for pool in (held["kv"].shape, held["kvw"].shape):
        rows = f"{math.prod(pool[:-1])},{pool[-1]}"
        assert sorted(_made(text, math.prod(pool))) == [
            ("fusion", rows), ("scatter", rows)], _made(text,
                                                       math.prod(pool))
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 2.147e9 + 2.181e9
    assert mem.temp_size_in_bytes < 0.05e9
    assert total + runner.staging_bytes + AFMOE_REFERENCE_BYTES < 16.9e9, \
        total


def test_afmoe_chunk_program_runs_the_band_and_carries_no_holder(
        afmoe_runner, monkeypatch):
    """The one prefill program: a chunk of 2,048 positions over the
    staging (the full layer's 26,624 positions and the window layers' ring
    of 6,144: 0.42e9 bytes, donated and returned), no holder among its
    operands; the band kernel in the 4 sliding layers and the causal one in
    the full layer; 8,192 assignments through megablox, carried there and
    back only as far as ``held_rows``; the ids (4, 2048, 4)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = afmoe_runner
    staging = jax.tree.map(lambda s: on_chip(s.shape, s.dtype),
                           runner.staging_spec)
    assert {k: v.shape for k, v in staging.items()} == {
        "k": (1, 26624, 1024), "v": (1, 26624, 1024),
        "kw": (4, 6144, 1024), "vw": (4, 6144, 1024)}
    assert runner.staging_bytes == 419_430_400
    lowered = runner._prefill_chunk.lower(
        None, weights, staging, on_chip((1, 2048), jnp.int32),
        on_chip((), jnp.int32), on_chip((), jnp.int32))
    ids = jax.tree.leaves(lowered.out_info)[-1]
    assert ids.shape == (4, 2048, 4) and ids.dtype == jnp.int32
    compiled = lowered.compile()
    text = compiled.as_text()
    kernels = re.findall(r'custom_call_target="tpu_custom_call".*'
                         r'op_name="[^"]*/(\w+)/pallas_call"', text)
    assert kernels.count("band_prefill") == 4
    assert kernels.count("causal_prefill") == 1
    assert len(re.findall(r"%gmm[.\d]* = bf16\[8192,3072\]", text)) == 12
    # 32 of 256 experts are held: the rows' way out and back stops at
    # ``held_rows`` in each of the 4 routed layers (PR 56)
    assert kernels.count("spread_held_rows") == 4
    assert kernels.count("sum_held_slots") == 4
    assert "ragged_dot_tiling" not in text
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 0.419e9           # the staging
    assert mem.temp_size_in_bytes < 0.5e9
    pools = sum(math.prod(h.shape) * 4 for h in held.values())
    assert total + pools + AFMOE_REFERENCE_BYTES < 16.9e9, total


# --------------------------------------------------- the Ling cell's programs
def _latent_decode(q, pool, layer, tables, lens, row):
    from ray_tpu.ops.paged_attention import latent_attention_decode
    return latent_attention_decode(q, pool, layer, tables, lens, row, 512)


def test_latent_decode_kernel_at_decode_shape(v5e, monkeypatch):
    """64 rows, 32 absorbed query heads of 576 lanes, a float32 latent pool
    of one plane and 64-position blocks handed over whole, a table of 240
    columns: a page is (64, 640), whole tiles, copied once for keys and
    values; nothing of the pool's size is made on the way in."""
    from ray_tpu.serve.llm.kv_cache import device_shape
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pool = device_shape(2048, 1, 64, 1, 576, planes=1)
    assert pool == (1, 1, 2048, 64, 640)
    text = _compile(_latent_decode, v5e, ((64, 32, 576), jnp.bfloat16),
                    (pool, jnp.float32), ((), jnp.int32),
                    ((64, 240), jnp.int32), ((64,), jnp.int32),
                    ((64, 576), jnp.bfloat16))
    assert "paged_decode_latent" in text
    assert not _made(text, math.prod(pool))


def _kda_rows(store, rows, q, k, v, g, beta):
    from ray_tpu.ops.delta_rule import kda_step_rows
    return kda_step_rows(store, 3, rows, q, k, v, g, beta)


def test_the_rows_of_state_are_stepped_in_place(v5e, monkeypatch):
    """64 of a store's 65 rows of 32 x 128 x 128 float32, one layer of six:
    the Pallas kernel reads and writes a row's heads where they lie, the
    store aliased to the result; no copy of the store or of the rows."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    store = (6, 65, 32, 128, 128)
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in (
        (store, jnp.float32), ((64,), jnp.int32),
        ((64, 32, 128), jnp.bfloat16), ((64, 32, 128), jnp.bfloat16),
        ((64, 32, 128), jnp.bfloat16), ((64, 32, 128), jnp.float32),
        ((64, 32), jnp.float32))]
    compiled = jax.jit(_kda_rows, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert "kda_step_rows" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == math.prod(store) * 4
    assert mem.temp_size_in_bytes < 0.05e9
    assert not _made(text, math.prod(store), 64 * 32 * 128 * 128)


def _kda_chunks(q, k, v, g, beta, state):
    from ray_tpu.ops.delta_rule import kda_chunks
    return kda_chunks(q, k, v, g, beta, state)


def test_the_per_channel_rule_compiles_at_a_prefill_chunk(v5e):
    """A chunk of 2,048 positions, 32 heads of 128, bf16: plain XLA (no
    Mosaic kernel), and nothing as large as a (C, C) matrix a channel."""
    shape = (1, 2048, 32, 128)
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in (
        (shape, jnp.bfloat16), (shape, jnp.bfloat16), (shape, jnp.bfloat16),
        (shape, jnp.float32), (shape[:3], jnp.float32),
        ((1, 32, 128, 128), jnp.float32))]
    compiled = jax.jit(_kda_chunks).lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


@pytest.fixture(scope="module")
def ling_runner(v5e):
    """The cell's runner over abstract weights, and what its holder holds
    as shapes on the chip: a K/V pool of no layer, a latent pool of one
    plane over the one MLA layer, and a store of state rows over the 6 KDA
    layers."""
    import json
    from pathlib import Path

    from ray_tpu.models import ling
    from ray_tpu.serve.llm import EngineConfig
    from ray_tpu.serve.llm.config import resolve_model
    from ray_tpu.serve.llm.kv_cache import device_shape
    from ray_tpu.serve.llm.model_runner import ModelRunner
    engine = json.loads((Path(__file__).parent.parent / "perfbench" /
                         "configs" / "ling-3.0-flash-vl.json").read_text()
                        )["serve"]["engine"]
    for key in ("decode_batch_buckets", "prefill_len_buckets"):
        engine[key] = tuple(engine[key])
    ecfg = EngineConfig(**engine)
    mod, mcfg = resolve_model(ecfg)
    assert mod is ling

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda key: mod.init_params(key, mcfg),
                            jax.random.key(0))
    runner = ModelRunner(ecfg, params=params)
    assert runner.params is params      # drawn in its serving type
    assert (runner.kv_layers, runner.latent_layers, runner.state_layers,
            runner.latent_dim, runner.chunk) == (0, 1, 6, 576, 2048)
    held = {
        "kv": on_chip(device_shape(ecfg.num_blocks, 0, ecfg.block_size,
                                   runner.n_kv, runner.head_dim),
                      jnp.float32),
        "latent": on_chip(device_shape(ecfg.num_blocks, 1, ecfg.block_size,
                                       1, runner.latent_dim, planes=1),
                          jnp.float32),
        "state": {name: on_chip((runner.state_layers, ecfg.max_num_seqs + 1)
                                + s.shape, s.dtype)
                  for name, s in runner.state_spec.items()}}
    weights = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), params)
    return runner, ecfg, held, weights, on_chip


# the float32 reference beside the engine: a block of 4 experts widened,
# their hidden rows at the check's 6,152 positions, a block of 256
# queries' scores over them in 32 heads, and a layer's other leaves
LING_REFERENCE_BYTES = 1.0e9


def test_ling_decode_program_steps_rows_and_walks_latent_pages(
        ling_runner, monkeypatch):
    """The cell's decode step at its one bucket of 64 (7 layers at the
    published widths): the absorbed kernel once (``paged_decode_latent``,
    over the latent pool of one plane and the one table), the 6 KDA
    layers' rows stepped in place in the donated store
    (``kda_step_rows``, and ``conv_step_rows`` for the tails); the latent
    pool and the
    store donated (1.34e9 + 0.88e9 bytes), the pool touched by the update
    of the step's 64 rows alone; 512 assignments through megablox, carried
    there and back only as far as the held rows; the ids come back as (6,
    64, 8) with the count of held experts touched behind the step's 64
    ids."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = ling_runner
    bucket, i32 = ecfg.decode_batch_buckets[-1], jnp.int32
    assert runner.param_bytes == 5_607_825_152
    lowered = runner._decode.lower(
        held, weights, on_chip((bucket,), i32), on_chip((bucket,), i32),
        on_chip((bucket, ecfg.max_blocks_per_seq), i32),
        on_chip((bucket,), i32), on_chip((), i32),
        on_chip((bucket,), i32), on_chip((bucket,), i32),
        on_chip((bucket,), i32))
    ids = jax.tree.leaves(lowered.out_info)[-1]
    assert ids.shape == (6, 64, 8) and ids.dtype == jnp.int32
    compiled = lowered.compile()
    text = compiled.as_text()
    root = next(line for line in text[text.index("ENTRY "):].splitlines()
                if " ROOT " in line)
    assert "s32[65]" in root and "s32[64]" in root and "s32[6,64,8]" in root
    kernels = re.findall(r'custom_call_target="tpu_custom_call".*'
                         r'op_name="[^"]*/(\w+)/pallas_call"', text)
    assert kernels.count("paged_decode_latent") == 1
    assert kernels.count("spread_held_rows") == 6
    assert held["latent"].shape == (1, 1, 8192, 64, 640)
    assert held["state"]["s"].shape == (6, 65, 32, 128, 128)
    assert held["state"]["conv"].shape == (6, 65, 288, 128)
    # the rows of S are stepped where they lie, once a KDA layer: nothing
    # the size of the bucket's 64 rows is gathered out or scattered back
    assert kernels.count("kda_step_rows") == 6
    assert kernels.count("conv_step_rows") == 6
    assert not _made(text, 64 * 32 * 128 * 128, 64 * 3 * 12288)
    pool = held["latent"].shape
    rows = f"{math.prod(pool[:-1])},{pool[-1]}"
    assert sorted(_made(text, math.prod(pool))) == [
        ("fusion", rows), ("scatter", rows)], _made(text, math.prod(pool))
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 1.342e9 + 0.875e9
    assert mem.temp_size_in_bytes < 0.5e9
    assert total + runner.staging_bytes + LING_REFERENCE_BYTES < 16.9e9, total


def test_ling_chunk_program_carries_state_and_stages_latent_rows(
        ling_runner, monkeypatch):
    """The one prefill program: a chunk of 2,048 positions over the staging
    (the MLA layer's latent rows of 16,384 positions, 37.7e6 bytes, donated
    and returned) and through the donated holder (the state from the
    store's staging row and back into it); the causal flash kernel once,
    over the up-projected rows at 256 lanes a head; the rule in plain XLA;
    16,384 assignments through megablox; the ids (6, 2048, 8)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = ling_runner
    staging = jax.tree.map(lambda s: on_chip(s.shape, s.dtype),
                           runner.staging_spec)
    assert {k: v.shape for k, v in staging.items()} == {
        "latent": (1, 16384, 576)}
    assert runner.staging_bytes == 37_748_736
    lowered = runner._prefill_chunk.lower(
        held, weights, staging, on_chip((1, 2048), jnp.int32),
        on_chip((), jnp.int32), on_chip((), jnp.int32))
    ids = jax.tree.leaves(lowered.out_info)[-1]
    assert ids.shape == (6, 2048, 8) and ids.dtype == jnp.int32
    compiled = lowered.compile()
    text = compiled.as_text()
    kernels = re.findall(r'custom_call_target="tpu_custom_call".*'
                         r'op_name="[^"]*/(\w+)/pallas_call"', text)
    assert kernels.count("causal_prefill") == 1
    assert kernels.count("spread_held_rows") == 6
    assert kernels.count("sum_held_slots") == 6
    assert len(re.findall(r"%gmm[.\d]* = bf16\[\d+,(?:768|2560)\]",
                          text)) == 18
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 1.342e9 + 0.875e9 + 0.037e9
    assert mem.temp_size_in_bytes < 1.5e9
    assert total + LING_REFERENCE_BYTES < 16.9e9, total


# ------------------------------------------------- the SDAR cell's programs
def _paged_block(q, kv_pool, layer, tables, lens, k_new, v_new):
    from ray_tpu.ops.paged_attention import _block_decode_kernel
    return _block_decode_kernel(q, kv_pool, layer, tables, lens, k_new,
                                v_new)


def test_block_decode_kernel_at_the_sdar_cells_shape(v5e):
    """32 rows of a block of 4 positions, 32 query / 4 KV heads of 128, the
    cell's float32 pool of 4,096 pages over six layers handed over whole
    and a table of 256 columns: a (row, KV head) a grid step, 32 query rows
    against the head's own 128 lanes of a page.  Nothing of the pool's size
    is made on the way in."""
    from ray_tpu.serve.llm.kv_cache import device_shape
    q = ((32, 4, 32, 128), jnp.bfloat16)
    new = ((32, 4, 4, 128), jnp.bfloat16)
    pool = device_shape(4096, 6, 16, 4, 128)
    text = _compile(_paged_block, v5e, q, (pool, jnp.float32),
                    ((), jnp.int32), ((32, 256), jnp.int32),
                    ((32,), jnp.int32), new, new)
    assert "paged_decode_block" in text
    assert not _made(text, math.prod(pool), math.prod(pool[1:]),
                     math.prod(pool[2:]))


@pytest.fixture(scope="module")
def sdar_runner(v5e):
    """The SDAR cell's runner over abstract weights (drawn in bf16, the
    serving type), and its K/V pool as a shape on the chip."""
    import json
    from pathlib import Path

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import EngineConfig
    from ray_tpu.serve.llm.config import resolve_model
    from ray_tpu.serve.llm.kv_cache import device_shape
    from ray_tpu.serve.llm.model_runner import ModelRunner
    engine = json.loads((Path(__file__).parent.parent / "perfbench" /
                         "configs" / "sdar-30b-a3b-chat.json").read_text()
                        )["serve"]["engine"]
    for key in ("decode_batch_buckets", "prefill_len_buckets"):
        engine[key] = tuple(engine[key])
    ecfg = EngineConfig(**engine)
    mod, mcfg = resolve_model(ecfg)
    assert mod is llama and mcfg.n_head * mcfg.head_dim == 2 * mcfg.n_embd

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda key: mod.init_params(key, mcfg),
                            jax.random.key(0))
    runner = ModelRunner(ecfg, params=params)
    assert runner.params is params      # drawn in its serving type
    assert runner.block == {"block": 4, "mask_id": 151669, "per_pass": 1}
    held = {"kv": on_chip(device_shape(ecfg.num_blocks, runner.kv_layers,
                                       ecfg.block_size, mcfg.n_kv_head,
                                       mcfg.head_dim), jnp.float32)}
    weights = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), params)
    return runner, ecfg, held, weights, on_chip


# the float32 reference widens a layer's experts a block of 8 at a time and
# holds the 151,936 x 2,048 head in float32 beside the engine it checks
SDAR_REFERENCE_BYTES = 1.25e9 + 0.2e9


def test_sdar_block_step_program_fits_and_writes_by_rows(sdar_runner,
                                                         monkeypatch):
    """The cell's pass at its one bucket of 32 rows x 4 positions (6 layers
    at the published widths, every expert, the whole head): the block
    kernel once in the layer scan's body, reading the pool where it lies;
    the pool donated and written by the commit rows' 128 slots alone; what
    goes out for the host is ONE int32 array, 32 x 4 ids, confidences' bits
    and flags with the count of touched experts behind them, and the block
    again, ids and flags, for the pass enqueued behind this one; the chosen
    experts come back as (6, 128, 8); and everything fits the chip with
    the check's reference beside it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = sdar_runner
    bucket, i32 = ecfg.decode_batch_buckets[-1], jnp.int32
    assert runner.param_bytes == 8_722_111_488
    last = (on_chip((bucket, 4), i32), on_chip((bucket, 4), jnp.bool_))
    lowered = runner._decode.lower(
        held, weights, on_chip((bucket, 4), i32), on_chip((bucket,), i32),
        on_chip((bucket, ecfg.max_blocks_per_seq), i32),
        on_chip((bucket,), i32), on_chip((), i32), last,
        on_chip((bucket,), i32), on_chip((bucket, 4), jnp.bool_),
        on_chip((bucket,), jnp.bool_))
    ids = jax.tree.leaves(lowered.out_info)[-1]
    assert ids.shape == (6, 128, 8) and ids.dtype == jnp.int32
    compiled = lowered.compile()
    text = compiled.as_text()
    root = next(line for line in text[text.index("ENTRY "):].splitlines()
                if " ROOT " in line)
    assert "s32[385]" in root and "s32[32,4]" in root \
        and "pred[32,4]" in root and "s32[6,128,8]" in root
    assert "paged_decode_block" in text
    # the experts stay outside the scan's slices (llama._split_experts):
    # nothing the size of a layer's experts is made (sliced out of the
    # stack each of 18 would be a 0.4 GB copy: 22 of the cell's first
    # trace's 40 ms a pass)
    assert not re.search(r"= bf16\[128,(2048,768|768,2048)\]", text)
    assert held["kv"].shape == (6, 2, 4096, 16, 512)
    _assert_the_pool_is_read_in_place_and_written_by_rows(
        text, held["kv"].shape, lanes_used=4 * 128)
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 1.6e9
    assert total + SDAR_REFERENCE_BYTES < 16.9e9, total


def test_sdar_prefill_program_fits_at_bucket_4096(sdar_runner, monkeypatch):
    """The prefill program at the largest bucket: flash attention at 32
    heads x 128 under the block mask, 32,768 assignments through the
    grouped matmuls, the last block's logits (1, 4, 151936), the ids
    (6, 4096, 8); it is handed no holder."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, _, held, weights, on_chip = sdar_runner
    lowered = runner._prefill.lower(
        None, weights, on_chip((1, 4096), jnp.int32), on_chip((), jnp.int32))
    ids = jax.tree.leaves(lowered.out_info)[-1]
    assert ids.shape == (6, 4096, 8) and ids.dtype == jnp.int32
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "flash_fwd" in text and "f32[1,4,151936]" in text
    assert not re.search(r"= bf16\[128,(2048,768|768,2048)\]", text)
    total, _ = _held_bytes(compiled)
    # beside the pool the engine holds while a prompt runs
    assert total + 1.61e9 + SDAR_REFERENCE_BYTES < 16.9e9, total


# --------------------------------------------- Keye: attention under an index
@pytest.fixture(scope="module")
def keye_runner(v5e):
    """The Keye cell's runner over abstract weights (drawn in bf16, the
    serving type), its K/V pool and its index plane as shapes on the
    chip."""
    import json
    from pathlib import Path

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import EngineConfig
    from ray_tpu.serve.llm.config import resolve_model
    from ray_tpu.serve.llm.kv_cache import device_shape
    from ray_tpu.serve.llm.model_runner import ModelRunner
    engine = json.loads((Path(__file__).parent.parent / "perfbench" /
                         "configs" / "keye-vl-2.0-30b-a3b.json").read_text()
                        )["serve"]["engine"]
    for key in ("decode_batch_buckets", "prefill_len_buckets"):
        engine[key] = tuple(engine[key])
    ecfg = EngineConfig(**engine)
    mod, mcfg = resolve_model(ecfg)
    assert mod is llama and mcfg.index_topk == 2048

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda key: mod.init_params(key, mcfg),
                            jax.random.key(0))
    runner = ModelRunner(ecfg, params=params)
    assert runner.params is params      # drawn in its serving type
    assert runner.chunk == 2048 and runner.block is None
    kept = runner.family.kept
    assert (kept.kv_layers, kept.index_layers, kept.index_dim) == (6, 6, 64)
    held = {"kv": on_chip(device_shape(ecfg.num_blocks, 6, ecfg.block_size,
                                       mcfg.n_kv_head, mcfg.head_dim),
                          jnp.float32),
            "index": on_chip(device_shape(ecfg.num_blocks, 6,
                                          ecfg.block_size, 1, 64, planes=1),
                             jnp.float32)}
    weights = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), params)
    return runner, ecfg, held, weights, on_chip


# the float32 reference beside the engine: a block of 256 queries' index
# products and scores over the check's 6,152 positions, eight experts
# widened, a quarter of the head in float32
KEYE_REFERENCE_BYTES = 1.2e9


def test_keye_decode_program_reads_positions_and_fits(keye_runner,
                                                      monkeypatch):
    """The cell's decode step at its one bucket of 4 (6 layers at the
    published widths, every expert, the whole head; 1,664 pages of 64):
    the only Mosaic kernels are the experts' grouped matmuls and the cut's
    counting passes (``index_topk_cut``, the step's rows one tile); what
    is sorted is ONE int32 key a scored entry, never the float32 scores;
    the K/V pool
    (2.62e9 bytes) and the index plane (0.33e9) donated and each touched by
    the update of the step's 4 rows alone; nothing of the pool's size is
    made and no layer's K or V sliced out: the chosen positions' rows are
    gathered from the pool where it lies, and NO copy of the pool in a
    narrower type stands before the layers' loop (the first compile made
    one, 1.3e9 bytes a step); K goes out with the index key as a fifth
    head; the program's temporaries stay under 0.01e9 bytes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = keye_runner
    bucket, i32 = ecfg.decode_batch_buckets[-1], jnp.int32
    assert runner.param_bytes == 8_749_244_928
    assert held["kv"].shape == (6, 2, 1664, 64, 512)
    assert held["index"].shape == (6, 1, 1664, 64, 128)
    lowered = runner._decode.lower(
        held, weights, on_chip((bucket,), i32), on_chip((bucket,), i32),
        on_chip((bucket, ecfg.max_blocks_per_seq), i32),
        on_chip((bucket,), i32), on_chip((), i32),
        on_chip((bucket,), i32), on_chip((bucket,), i32))
    out = jax.tree.leaves(lowered.out_info)
    assert out[-1].shape == (6, 4, 8) and out[-1].dtype == jnp.int32
    assert out[-3].shape == (6, 4, 5, 128) and out[-2].shape == (6, 4, 4, 128)
    compiled = lowered.compile()
    text = compiled.as_text()
    for scope in ("index_proj", "index_score", "index_topk", "attn_indexed"):
        assert f"/{scope}/" in text, scope
    assert not re.search(r"= bf16\[6,2,1664,64,512\]", text)
    assert not re.search(r"= bf16\[128,(2048,768|768,2048)\]", text)
    assert "index_topk_cut" in text
    # (the router's top 8 of 128 and the experts' dispatch sort too)
    sorts = [found for line in text.splitlines() if " sort(" in line
             for found in re.findall(r"(\w+)\[4,(\d{4,})\]",
                                     line.split(" sort(")[0])]
    assert sorts == [("s32", str(416 * 64 + 1))], sorts
    _assert_the_pool_is_read_in_place_and_written_by_rows(
        text, held["kv"].shape, lanes_used=4 * 128)
    plane = held["index"].shape
    rows = f"{math.prod(plane[:-1])},{plane[-1]}"
    assert sorted(_made(text, math.prod(plane))) == [
        ("fusion", rows), ("scatter", rows)]
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 2.62e9 + 0.32e9
    assert mem.temp_size_in_bytes < 0.01e9
    assert total + 0.74e9 + KEYE_REFERENCE_BYTES < 16.9e9, total


def test_keye_chunk_program_is_one_and_carries_no_holder(keye_runner,
                                                         monkeypatch):
    """The one prefill program: a chunk of 2,048 positions over a staging
    of 26,624 (K, V and index keys: 0.74e9 bytes, donated and returned);
    in the layer scan's body the score kernel, the cut's kernel and the
    masked flash kernel beside the experts' three and, since PR 68, the
    router's choice of 8 in 128 (``router_choice``: 2,048 rows are whole
    tiles, a decode step's 4 are not); it is handed no holder,
    so the pools go untouched; its temporaries (the (2,048, 26,624) scores,
    their keys and the mask a position wide) stay under 1.0e9 bytes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = keye_runner
    staging = jax.tree.map(lambda s: on_chip(s.shape, s.dtype),
                           runner.staging_spec)
    assert staging["k"].shape == (6, 26624, 512)
    assert staging["index"].shape == (6, 26624, 128)
    assert runner.staging_bytes == 6 * 26624 * (512 + 512 + 128) * 4
    i32 = jnp.int32
    lowered = runner._prefill_chunk.lower(
        None, weights, staging, on_chip((1, runner.chunk), i32),
        on_chip((), i32), on_chip((), i32))
    ids = jax.tree.leaves(lowered.out_info)[-1]
    assert ids.shape == (6, 2048, 8) and ids.dtype == jnp.int32
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 7
    for kernel in ("index_score", "index_topk_cut", "sparse_prefill",
                   "router_choice"):
        assert kernel in text, kernel
    assert not re.search(r"= bf16\[128,(2048,768|768,2048)\]", text)
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 0.73e9        # the staging
    assert mem.temp_size_in_bytes < 1.0e9
    # beside the pools the engine holds while a prompt runs
    assert total + 2.95e9 + KEYE_REFERENCE_BYTES < 16.9e9, total


@pytest.mark.parametrize("bucket", [8192, 26624])
def test_keye_scatter_program_writes_both_planes_by_rows(keye_runner,
                                                         monkeypatch, bucket):
    """A prompt's scatter at the smallest and the largest bucket: K comes
    with the index key as a fifth head and leaves it to the index plane;
    both pools are donated and written by rows."""
    from ray_tpu.serve.llm.kv_cache import _programs
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = keye_runner
    f32, i32 = jnp.float32, jnp.int32
    compiled = _programs().scatter_prefill.lower(
        held, on_chip((bucket // ecfg.block_size,), i32),
        on_chip((6, bucket, 5, 128), f32), on_chip((6, bucket, 4, 128), f32),
        on_chip((), i32)).compile()
    text = compiled.as_text()
    assert not _made(text, math.prod(held["kv"].shape[1:]))
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 2.62e9 + 0.32e9
    assert mem.temp_size_in_bytes < 1.4e9 * bucket / 26624 + 0.1e9
    # beside the weights and the staging
    assert total + 8.75e9 + 0.74e9 < 16.9e9, total


@pytest.fixture(scope="module")
def evabyte_runner(v5e):
    """The EvaByte cell's runner over abstract weights (drawn in bf16, the
    serving type) and its ONE K/V pool as a shape on the chip: folded rows
    lie in it as K/V rows do."""
    import json
    from pathlib import Path

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import EngineConfig
    from ray_tpu.serve.llm.config import resolve_model
    from ray_tpu.serve.llm.kv_cache import device_shape
    from ray_tpu.serve.llm.model_runner import ModelRunner
    engine = json.loads((Path(__file__).parent.parent / "perfbench" /
                         "configs" / "evabyte-6.5b.json").read_text()
                        )["serve"]["engine"]
    for key in ("decode_batch_buckets", "prefill_len_buckets"):
        engine[key] = tuple(engine[key])
    ecfg = EngineConfig(**engine)
    mod, mcfg = resolve_model(ecfg)
    assert mod is llama and (mcfg.eva_window, mcfg.eva_chunk) == (2048, 16)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.eval_shape(lambda key: mod.init_params(key, mcfg),
                            jax.random.key(0))
    runner = ModelRunner(ecfg, params=params)
    assert runner.params is params      # drawn in its serving type
    assert runner.chunk == 2048 and runner.block is None
    kept = runner.family.kept
    assert (kept.kv_layers, kept.fold_window, kept.fold_chunk) == (8, 2048, 16)
    held = {"kv": on_chip(device_shape(ecfg.num_blocks, 8, ecfg.block_size,
                                       mcfg.n_kv_head, mcfg.head_dim),
                          jnp.float32)}
    weights = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), params)
    return runner, ecfg, held, weights, on_chip


# the float32 reference beside the engine: a layer's seven matrices widened
# (0.81e9), a block of 512 queries' scores over the check's 4,098 positions
# in 32 heads, the stream and q, k, v in float32
EVABYTE_REFERENCE_BYTES = 1.6e9


def test_evabyte_decode_program_walks_the_shrunk_table_and_fits(
        evabyte_runner, monkeypatch):
    """The cell's decode step at its one bucket of 8 (8 layers at the
    published widths, the whole head of 8 x 320; 448 pages of 64 rows x
    4,096 lanes): ONE Mosaic kernel in the layer scan's body, the plain
    paged walk at 32 / 32 heads of 128 (no new decode kernel: to the query
    a folded row is one more key and value); the rows a sequence holds are
    reckoned from the positions it has seen inside the program; the pool
    (7.52e9 bytes) donated and touched by the update of the step's 8 rows
    alone; head 0's 320 logits and no other leave it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = evabyte_runner
    bucket, i32 = ecfg.decode_batch_buckets[-1], jnp.int32
    assert runner.param_bytes == 3_261_865_984
    assert held["kv"].shape == (8, 2, 448, 64, 4096)
    lowered = runner._decode.lower(
        held, weights, on_chip((bucket,), i32), on_chip((bucket,), i32),
        on_chip((bucket, ecfg.max_blocks_per_seq), i32),
        on_chip((bucket,), i32), on_chip((), i32),
        on_chip((bucket,), i32), on_chip((bucket,), i32))
    out = jax.tree.leaves(lowered.out_info)
    assert out[0].shape == (8, 2, 448, 64, 4096)
    assert (8, 320) in [o.shape for o in out]
    assert out[-1].shape == (8, 8, 32, 128)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "/attn_eva/" in text
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    _assert_the_pool_is_read_in_place_and_written_by_rows(
        text, held["kv"].shape, lanes_used=32 * 128)
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 7.5e9
    assert mem.temp_size_in_bytes < 0.05e9
    assert total + 0.94e9 + EVABYTE_REFERENCE_BYTES < 16.9e9, total


def test_evabyte_chunk_program_is_one_window_and_folds_it(evabyte_runner,
                                                         monkeypatch):
    """The one prefill program: a chunk of 2,048 positions = one window over
    a staging of the 3,584 rows a prompt of 26,624 HOLDS (1,536 folded and
    one window, K and V: 0.94e9 bytes, donated and returned); the causal
    flash kernel in the coordinates of the rows held, the fold under its
    own scope; it is handed no holder, so the pool goes untouched."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = evabyte_runner
    staging = jax.tree.map(lambda s: on_chip(s.shape, s.dtype),
                           runner.staging_spec)
    assert staging["k"].shape == (8, 3584, 4096) and set(staging) == {"k", "v"}
    assert runner.staging_bytes == 8 * 3584 * 4096 * 2 * 4
    i32 = jnp.int32
    compiled = runner._prefill_chunk.lower(
        None, weights, staging, on_chip((1, runner.chunk), i32),
        on_chip((), i32), on_chip((), i32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "causal_prefill" in text
    for scope in ("attn_eva", "eva_fold", "kv_stage"):
        assert f"/{scope}/" in text, scope
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 0.93e9        # the staging
    assert mem.temp_size_in_bytes < 0.6e9
    # beside the pool the engine holds while a prompt runs
    assert total + 7.52e9 + EVABYTE_REFERENCE_BYTES < 16.9e9, total


def test_evabyte_fold_program_reads_one_window_and_writes_by_rows(
        evabyte_runner, monkeypatch):
    """The fold of a window that closes in decode: the window's 32 pages
    gathered out of the donated pool in every layer (0.54e9 bytes), 128
    folded rows a layer written over the first two of them by rows, and
    nothing of the pool's size made beside that update."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = evabyte_runner
    compiled = runner._fold.lower(
        held, weights, on_chip((2048 // ecfg.block_size,), jnp.int32)
    ).compile()
    text = compiled.as_text()
    assert "/eva_fold/" in text and "tpu_custom_call" not in text
    pool = held["kv"].shape
    rows = f"{math.prod(pool[:-1])},{pool[-1]}"
    assert sorted(_made(text, math.prod(pool))) == [
        ("fusion", rows), ("scatter", rows)], _made(text, math.prod(pool))
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 7.5e9
    assert mem.temp_size_in_bytes < 1.7e9
    assert total + 0.94e9 < 16.9e9, total


@pytest.mark.parametrize("bucket", [8192, 26624])
def test_evabyte_scatter_program_writes_the_rows_held(evabyte_runner,
                                                      monkeypatch, bucket):
    """A prompt's scatter at the smallest and the largest bucket: what it
    is handed are the rows the prompt HOLDS (2,432 for up to 8,192
    positions, 3,584 for up to 26,624), written by rows into the donated
    pool."""
    from ray_tpu.serve.llm.kv_cache import _programs, held_rows_most
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner, ecfg, held, weights, on_chip = evabyte_runner
    rows = held_rows_most(bucket, 2048, 16)
    assert rows == {8192: 2432, 26624: 3584}[bucket]
    f32, i32 = jnp.float32, jnp.int32
    compiled = _programs().scatter_prefill.lower(
        held, on_chip((rows // ecfg.block_size,), i32),
        on_chip((8, rows, 32, 128), f32), on_chip((8, rows, 32, 128), f32),
        on_chip((), i32)).compile()
    total, mem = _held_bytes(compiled)
    assert mem.alias_size_in_bytes >= 7.5e9
    # K and V stacked, lane-flat, once: twice the rows handed
    assert mem.temp_size_in_bytes < 2.1 * 2 * 8 * rows * 4096 * 4
    # beside the weights and the staging the rows were cut from
    assert total + 3.26e9 + 0.94e9 < 16.9e9, total
