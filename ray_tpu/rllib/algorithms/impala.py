"""IMPALA: async actor-learner with V-trace off-policy correction.

Reference: ``rllib/algorithms/impala/`` (SURVEY.md §3.5) — rollout actors
continuously push batches to a learner queue; the learner applies V-trace
(Espeholt et al. 2018) to correct for policy lag, then broadcasts weights.
Rebuilt: the "queue" is the object store — each worker keeps exactly one
in-flight ``sample_with_weights`` future; the learner drains ready futures
with ``ray_tpu.wait`` and re-issues them carrying the freshest weights ref,
so sampling and the jitted learner step overlap without a learner thread.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
import optax

import ray_tpu
from ray_tpu.rllib.algorithms.algorithm import Algorithm, AlgorithmConfig
from ray_tpu.rllib.sample_batch import (
    ACTION_LOGP, ACTIONS, NEXT_OBS, OBS, REWARDS, SampleBatch, TERMINATEDS,
    TRUNCATEDS, concat_samples)


def vtrace(behavior_logp, target_logp, rewards, discounts, values,
           bootstrap_value, clip_rho: float = 1.0, clip_c: float = 1.0,
           clip_pg_rho: float = None):
    """V-trace targets + policy-gradient advantages.

    All inputs time-major ``[T, B]``; ``bootstrap_value`` is ``[B]``.
    Returns ``(vs [T,B], pg_advantages [T,B])``.  ``clip_pg_rho`` clips the
    importance weights of the pg advantages separately from the value
    targets (reference: vtrace_clip_pg_rho_threshold); defaults to
    ``clip_rho``.
    """
    rhos = jnp.exp(target_logp - behavior_logp)
    clipped_rhos = jnp.minimum(clip_rho, rhos)
    pg_rhos = jnp.minimum(
        clip_rho if clip_pg_rho is None else clip_pg_rho, rhos)
    cs = jnp.minimum(clip_c, rhos)
    values_next = jnp.concatenate(
        [values[1:], bootstrap_value[None]], axis=0)
    deltas = clipped_rhos * (rewards + discounts * values_next - values)

    def backward(acc, t):
        delta, discount, c = t
        acc = delta + discount * c * acc
        return acc, acc

    _, vs_minus_v = jax.lax.scan(
        backward, jnp.zeros_like(bootstrap_value),
        (deltas, discounts, cs), reverse=True)
    vs = values + vs_minus_v
    vs_next = jnp.concatenate([vs[1:], bootstrap_value[None]], axis=0)
    pg_adv = pg_rhos * (rewards + discounts * vs_next - values)
    return vs, pg_adv


class IMPALAConfig(AlgorithmConfig):
    def __init__(self, algo_class=None):
        super().__init__(algo_class or IMPALA)
        self._cfg.update({
            "lr": 5e-4, "num_workers": 2, "rollout_fragment_length": 50,
            "vtrace_clip_rho_threshold": 1.0,
            "vtrace_clip_pg_rho_threshold": 1.0,
            "vf_loss_coeff": 0.5, "entropy_coeff": 0.01, "grad_clip": 40.0,
            "num_batches_per_iteration": 10,
            # Weight broadcast cadence in learner updates (reference:
            # impala broadcast_interval) — actors run stale-by-at-most-this
            # policies; V-trace corrects the lag.  Weight pull is a full
            # device→host transfer, the learner's most expensive host op.
            "broadcast_interval": 1,
            # Fragments concatenated (along B) per learner update —
            # amortizes per-dispatch overhead into bigger XLA programs
            # (reference: train_batch_size assembly from fragments).
            "num_fragments_per_update": 1,
            # "auto" (default backend) | "cpu".  cpu pins the learner jit
            # and its inputs to host CPU devices: correct when the
            # host-to-device link is thinner than the sample stream
            # (pixel fragments then upload slower than a host CPU can
            # just learn on them).
            "learner_device": "auto",
            # True = barrier sampling (wait for every worker, then learn)
            # — the A/B control proving the async path's actor/learner
            # overlap (benchmarks/rllib_bench.py impala_overlap).
            "sync_sampling": False,
        })


class IMPALA(Algorithm):
    _default_config_cls = IMPALAConfig

    @staticmethod
    def _policy_surrogate(config):
        """Policy-loss term over (target_logp, behavior_logp, pg_adv) —
        plain V-trace policy gradient here; APPO overrides with the
        clipped PPO surrogate."""
        def pg(target_logp, behavior_logp, pg_adv):
            return -(target_logp * pg_adv).mean()
        return pg

    def setup(self, config: Dict[str, Any]) -> None:
        policy = self.workers.local_worker.policy
        apply_fn = policy.apply_fn
        dist = policy.dist_class
        self._learner_dev = None
        if str(config.get("learner_device", "auto")) == "cpu" \
                and jax.default_backend() != "cpu":
            self._learner_dev = jax.devices("cpu")[0]
            # learner state lives on host: sample ingest skips the
            # accelerator interconnect entirely
            policy.params = jax.device_put(policy.params, self._learner_dev)
        self._optimizer = optax.chain(
            optax.clip_by_global_norm(config["grad_clip"]),
            optax.rmsprop(config["lr"], decay=0.99, eps=0.1))
        self._opt_state = self._optimizer.init(policy.params)
        gamma = float(config["gamma"])
        clip_rho = float(config["vtrace_clip_rho_threshold"])
        clip_pg_rho = float(config["vtrace_clip_pg_rho_threshold"])
        vf_coeff = float(config["vf_loss_coeff"])
        ent_coeff = float(config["entropy_coeff"])
        optimizer = self._optimizer

        surrogate = self._policy_surrogate(config)

        def loss_fn(params, batch):
            # batch cols are [T, B, ...]; flatten for the net, reshape back.
            T, B = batch[REWARDS].shape
            obs = batch[OBS].reshape((T * B,) + batch[OBS].shape[2:])
            inputs, values = apply_fn(params, obs)
            actions = batch[ACTIONS].reshape((T * B,))
            target_logp = dist.logp(inputs, actions).reshape((T, B))
            entropy = dist.entropy(inputs).mean()
            values = values.reshape((T, B))
            _, bootstrap = apply_fn(params, batch["last_obs"])
            discounts = gamma * (1.0 - batch["dones"])
            vs, pg_adv = vtrace(
                batch[ACTION_LOGP], target_logp, batch[REWARDS],
                discounts, values, bootstrap, clip_rho,
                clip_pg_rho=clip_pg_rho)
            vs = jax.lax.stop_gradient(vs)
            pg_adv = jax.lax.stop_gradient(pg_adv)
            pi_loss = surrogate(target_logp, batch[ACTION_LOGP], pg_adv)
            vf_loss = 0.5 * jnp.square(vs - values).mean()
            total = pi_loss + vf_coeff * vf_loss - ent_coeff * entropy
            return total, (pi_loss, vf_loss, entropy)

        def update(params, opt_state, batch):
            grads, aux = jax.grad(loss_fn, has_aux=True)(params, batch)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            pi_loss, vf_loss, entropy = aux
            return params, opt_state, {
                "policy_loss": pi_loss, "vf_loss": vf_loss,
                "entropy": entropy}

        self._update = jax.jit(update)
        self._in_flight: Dict[Any, Any] = {}  # future -> worker
        self._trained_steps = 0
        self._weights_ref = None
        self._updates_since_broadcast = 0

    def _to_time_major(self, batch: SampleBatch) -> Dict[str, jnp.ndarray]:
        """Worker fragments arrive env-major ([env0 t0..T, env1 t0..T, ...]);
        reshape to [T, B] for vtrace.

        NEXT_OBS is NOT shipped to the device: V-trace only bootstraps from
        the final observation of each env row, so only that [B, ...] slice
        uploads — for pixel fragments this halves learner ingest bytes."""
        T = int(self.config["rollout_fragment_length"])
        B = batch.count // T
        put = (lambda a: jax.device_put(a, self._learner_dev)) \
            if self._learner_dev is not None else jnp.asarray
        out = {}
        for k in (OBS, ACTIONS, REWARDS, ACTION_LOGP):
            v = batch[k][:B * T]
            out[k] = put(v.reshape((B, T) + v.shape[1:]).swapaxes(0, 1))
        next_obs = batch[NEXT_OBS][:B * T]
        out["last_obs"] = put(
            next_obs.reshape((B, T) + next_obs.shape[1:])[:, -1])
        dones = (batch[TERMINATEDS] | batch[TRUNCATEDS])[:B * T]
        out["dones"] = put(
            dones.reshape((B, T)).swapaxes(0, 1).astype(np.float32))
        return out

    def _learn_on(self, batch: SampleBatch) -> Dict[str, Any]:
        """One async learner update; returns device scalars (NOT synced —
        forcing a host read per batch would serialize the device queue on
        the dispatch round-trip)."""
        policy = self.workers.local_worker.policy
        tm = self._to_time_major(batch)
        policy.params, self._opt_state, info = self._update(
            policy.params, self._opt_state, tm)
        self._trained_steps += batch.count
        return info

    def training_step(self) -> Dict[str, Any]:
        remotes = self.workers.remote_workers
        n_batches = int(self.config["num_batches_per_iteration"])
        dev_info: Dict[str, Any] = {}
        if not remotes:  # degenerate sync mode for tests
            for _ in range(n_batches):
                dev_info = self._learn_on(self.workers.local_worker.sample())
            info = {k: float(v) for k, v in dev_info.items()}
            info["num_env_steps_trained"] = self._trained_steps
            return info
        if bool(self.config.get("sync_sampling")):
            # Barrier mode — the A/B control for the actor/learner-overlap
            # benchmark (rllib_bench.py impala_overlap): broadcast, wait
            # for EVERY worker's fragment, learn, repeat.  The async path
            # below re-issues each worker the moment its fragment lands
            # and learns while the others are still sampling.
            from ray_tpu.rllib.evaluation import synchronous_parallel_sample
            for _ in range(n_batches):
                self.workers.sync_weights()
                dev_info = self._learn_on(
                    synchronous_parallel_sample(self.workers))
            info = {k: float(v) for k, v in dev_info.items()}
            info["num_env_steps_trained"] = self._trained_steps
            return info
        # Broadcast at most every `broadcast_interval` updates (reference:
        # IMPALA's broadcast_interval — actors run slightly stale policies
        # and V-trace corrects for the lag).  Pulling params off the device
        # per batch would cost a full device→host transfer + sync RTT per
        # 128-frame fragment.
        interval = max(1, int(self.config.get("broadcast_interval", 1)))
        per_update = max(1, int(self.config.get(
            "num_fragments_per_update", 1)))
        if self._weights_ref is None:
            self._weights_ref = ray_tpu.put(
                self.workers.local_worker.get_weights())
        for w in remotes:
            if w not in [v for v in self._in_flight.values()]:
                self._in_flight[w.sample_with_weights.remote(
                    self._weights_ref)] = w
        processed = 0
        pending: List[SampleBatch] = []
        while processed < n_batches:
            ready, _ = ray_tpu.wait(list(self._in_flight),
                                    num_returns=1)
            fut = ready[0]
            worker = self._in_flight.pop(fut)
            pending.append(ray_tpu.get(fut))
            # Re-issue immediately with the freshest broadcast ref.
            self._in_flight[worker.sample_with_weights.remote(
                self._weights_ref)] = worker
            if len(pending) < per_update:
                continue
            batch = pending[0] if len(pending) == 1 \
                else concat_samples(pending)
            pending = []
            dev_info = self._learn_on(batch)
            processed += 1
            self._updates_since_broadcast += 1
            if self._updates_since_broadcast >= interval:
                self._weights_ref = ray_tpu.put(
                    self.workers.local_worker.get_weights())
                self._updates_since_broadcast = 0
        # Single host sync for the whole iteration's metrics.
        info = {k: float(v) for k, v in dev_info.items()}
        info["num_env_steps_trained"] = self._trained_steps
        return info
