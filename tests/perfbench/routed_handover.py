"""What a program that routes owes the serving check, laid OVER the program.

``perfbench/jobs/serve.py`` holds a routed model by the experts its step
programs chose: the runner says ``route_spec`` ({"layers", "k"}) and keeps
``choices``, the ids of the step it ran last, int (routed layers, rows, k).
No PR that may edit the harness may edit the program, so at this tree the
program offers neither.  ``install`` supplies both from outside, for the
tests here and for a builder's script on the chip, from the run that makes
the logits: ``ops/moe.route_softmax``, which the program's experts layer
looks up when a step program is traced, is replaced by itself plus a host
callback that takes each layer's ``expert_idx`` as the device computes it,
and ``ModelRunner.prefill`` / ``.decode`` gather a step's layers into
``choices``.  A callback a layer is no way to serve; the program's own
hand-over returns the ids as one more result of the step.  Once a module
exports ``routed_layers`` the program has its own and ``install`` lays
nothing over it but the fault asked for.

``fault`` plants one of ``FAULTS`` where the choice is made, so that the
program both computes with it and reports it: each is a departure that is
not a rounding, and the check has to fail on every one.
"""

from __future__ import annotations

import numpy as np


def _far_expert(idx, weights, logits, probs, x, w_router, k):
    """Row 2's last pick is the expert scored lowest of all."""
    import jax.numpy as jnp
    if idx.shape[0] < 3:
        return idx, weights
    worst = jnp.argmin(probs[2]).astype(idx.dtype)
    return (idx.at[2, -1].set(worst),
            weights.at[2, -1].set(probs[2, worst]))


def _exchanged(idx, weights, logits, probs, x, w_router, k):
    """Rows 3 and 7 take each other's experts, weighed by their own p."""
    import jax.numpy as jnp
    if idx.shape[0] < 8:
        return idx, weights
    rows = jnp.arange(idx.shape[0]).at[3].set(7).at[7].set(3)
    idx = idx[rows]
    return idx, jnp.take_along_axis(probs, idx, axis=-1)


def _one_expert_short(idx, weights, logits, probs, x, w_router, k):
    """k - 1 experts computed: the last pick weighs nothing."""
    return idx, weights.at[:, -1].set(0.0)


def _renormalised(idx, weights, logits, probs, x, w_router, k):
    """The chosen weights sum to 1, which this family's rule does not do."""
    return idx, weights / weights.sum(-1, keepdims=True)


def _fp8_router(idx, weights, logits, probs, x, w_router, k):
    """The router's two operands in float8_e4m3, the precision below."""
    import jax
    import jax.numpy as jnp
    low = jnp.float8_e4m3fn
    logits = jnp.dot(x.astype(low).astype(jnp.float32),
                     w_router.astype(low).astype(jnp.float32))
    weights, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return idx, weights


FAULTS = {"far_expert": _far_expert, "exchanged": _exchanged,
          "one_expert_short": _one_expert_short,
          "renormalised": _renormalised, "fp8_router": _fp8_router}


def install(monkeypatch, fault: str | None = None) -> None:
    """Until ``monkeypatch`` undoes it: every ModelRunner built over a
    model with experts offers ``route_spec`` and ``choices``."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.ops import moe
    from ray_tpu.serve.llm.model_runner import ModelRunner

    own = hasattr(llama, "routed_layers")       # the program hands over
    route_softmax = moe.route_softmax
    taken: list = []                            # a step's ids, layer by layer

    def route(x, w_router, k):
        idx, weights, logits, probs = route_softmax(x, w_router, k)
        if fault:
            idx, weights = FAULTS[fault](idx, weights, logits, probs, x,
                                         w_router, k)
        if not own:
            jax.debug.callback(lambda ids: taken.append(np.asarray(ids)),
                               idx, ordered=True)
        return idx, weights, logits, probs

    monkeypatch.setattr(moe, "route_softmax", route)
    if own:
        return
    init, prefill, decode = (ModelRunner.__init__, ModelRunner.prefill,
                             ModelRunner.decode)

    def described(self, *args, **kwargs):
        init(self, *args, **kwargs)
        self.route_spec = {"layers": self.mcfg.n_layer,
                           "k": self.mcfg.experts_per_token} \
            if getattr(self.mcfg, "n_experts", 0) else None
        self.choices = None

    def gathering(step):
        def call(self, *args, **kwargs):
            taken.clear()
            out = step(self, *args, **kwargs)
            if self.route_spec:
                jax.effects_barrier()       # every layer's callback has run
                self.choices = np.stack(taken)
            return out
        return call

    monkeypatch.setattr(ModelRunner, "__init__", described)
    monkeypatch.setattr(ModelRunner, "prefill", gathering(prefill))
    monkeypatch.setattr(ModelRunner, "decode", gathering(decode))
