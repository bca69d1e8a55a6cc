"""Policy/value networks and action distributions — pure JAX.

Reference: ``rllib/models/`` catalog + ``ModelV2`` (SURVEY.md §2.5).  The
reference builds torch/tf modules; here networks are (init, apply) function
pairs over pytrees so the whole learner step jits into one XLA program —
the MXU sees a handful of batched matmuls per update, nothing else.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    obs_dim: int
    num_outputs: int          # logits dim (discrete: n; gaussian: 2*act_dim)
    hiddens: Tuple[int, ...] = (256, 256)
    free_log_std: bool = False
    # Pixel path (reference: rllib/models catalog CNNs): non-empty
    # conv_filters → a shared conv torso ((out_ch, kernel, stride) per
    # layer, VALID padding, relu) + dense head feeds separate linear
    # pi/vf (or Q) heads.  obs are NHWC uint8-scale [0,255]; the torso
    # divides by 255.
    obs_shape: Tuple[int, ...] = ()
    conv_filters: Tuple[Tuple[int, int, int], ...] = ()
    conv_dense: int = 512


# The Nature DQN / IMPALA torso (Mnih et al. 2015): the reference's
# default Atari conv stack in rllib/models/catalog.py.
NATURE_CNN_FILTERS = ((32, 8, 4), (64, 4, 2), (64, 3, 1))


def make_model_config(observation_space, action_space,
                      config: dict) -> ModelConfig:
    """Catalog entry point (reference: ModelCatalog): rank-3 Box obs get
    the Nature CNN unless ``config['conv_filters']`` overrides."""
    obs_shape = tuple(observation_space.shape)
    conv = config.get("conv_filters")
    if conv is None and len(obs_shape) == 3:
        conv = NATURE_CNN_FILTERS
    return ModelConfig(
        obs_dim=flat_obs_dim(observation_space),
        num_outputs=num_dist_inputs(action_space),
        hiddens=tuple(config.get("fcnet_hiddens", (256, 256))),
        obs_shape=obs_shape,
        conv_filters=tuple(tuple(f) for f in conv) if conv else (),
        conv_dense=int(config.get("conv_dense", 512)))


def _init_linear(key, fan_in, fan_out, scale=np.sqrt(2)):
    """Orthogonal init — the standard PPO-stability choice."""
    w = jax.random.orthogonal(key, max(fan_in, fan_out))[:fan_in, :fan_out]
    return {"w": (w * scale).astype(jnp.float32),
            "b": jnp.zeros((fan_out,), jnp.float32)}


def init_actor_critic(key: jax.Array, cfg: ModelConfig) -> Params:
    """Separate policy and value towers (reference default: two MLPs)."""
    sizes = (cfg.obs_dim, *cfg.hiddens)
    keys = jax.random.split(key, 2 * len(cfg.hiddens) + 2)
    params: Params = {}
    for tower in ("pi", "vf"):
        off = 0 if tower == "pi" else len(cfg.hiddens) + 1
        for i, (fi, fo) in enumerate(zip(sizes[:-1], sizes[1:])):
            params[f"{tower}_{i}"] = _init_linear(keys[off + i], fi, fo)
    params["pi_out"] = _init_linear(keys[len(cfg.hiddens)],
                                    sizes[-1], cfg.num_outputs, scale=0.01)
    params["vf_out"] = _init_linear(keys[-1], sizes[-1], 1, scale=1.0)
    return params


def actor_critic_apply(params: Params, obs: jax.Array,
                       num_hidden: int) -> Tuple[jax.Array, jax.Array]:
    """Returns (dist_inputs [B, num_outputs], values [B])."""
    x = obs
    for i in range(num_hidden):
        p = params[f"pi_{i}"]
        x = jnp.tanh(x @ p["w"] + p["b"])
    logits = x @ params["pi_out"]["w"] + params["pi_out"]["b"]
    v = obs
    for i in range(num_hidden):
        p = params[f"vf_{i}"]
        v = jnp.tanh(v @ p["w"] + p["b"])
    values = (v @ params["vf_out"]["w"] + params["vf_out"]["b"])[:, 0]
    return logits, values


# ------------------------------------------------------------- conv torso

def _conv_out_hw(hw: int, kernel: int, stride: int) -> int:
    return (hw - kernel) // stride + 1


def conv_torso_feature_dim(cfg: ModelConfig) -> int:
    return cfg.conv_dense


def init_conv_torso(key: jax.Array, cfg: ModelConfig) -> Params:
    """Shared conv feature net: conv stack (VALID, relu) → dense(relu)."""
    H, W, C = cfg.obs_shape
    keys = jax.random.split(key, len(cfg.conv_filters) + 1)
    params: Params = {}
    in_c = C
    for i, (out_c, k, s) in enumerate(cfg.conv_filters):
        fan_in = k * k * in_c
        w = jax.random.normal(keys[i], (k, k, in_c, out_c), jnp.float32)
        params[f"conv_{i}"] = {"w": w * np.sqrt(2.0 / fan_in),
                               "b": jnp.zeros((out_c,), jnp.float32)}
        H, W, in_c = _conv_out_hw(H, k, s), _conv_out_hw(W, k, s), out_c
    params["dense"] = _init_linear(keys[-1], H * W * in_c, cfg.conv_dense)
    return params


def conv_torso_apply(params: Params, obs: jax.Array,
                     cfg: ModelConfig) -> jax.Array:
    """(B, H, W, C) [0,255] → (B, conv_dense) relu features."""
    x = obs.astype(jnp.float32) / 255.0
    for i, (_, _, s) in enumerate(cfg.conv_filters):
        p = params[f"conv_{i}"]
        x = jax.lax.conv_general_dilated(
            x, p["w"], window_strides=(s, s), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]
        x = jax.nn.relu(x)
    x = x.reshape(x.shape[0], -1)
    p = params["dense"]
    return jax.nn.relu(x @ p["w"] + p["b"])


def init_actor_critic_conv(key: jax.Array, cfg: ModelConfig) -> Params:
    """Shared conv torso + separate linear pi/vf heads (the reference's
    Atari actor-critic shape)."""
    kt, kp, kv = jax.random.split(key, 3)
    feat = conv_torso_feature_dim(cfg)
    return {"torso": init_conv_torso(kt, cfg),
            "pi_out": _init_linear(kp, feat, cfg.num_outputs, scale=0.01),
            "vf_out": _init_linear(kv, feat, 1, scale=1.0)}


def actor_critic_conv_apply(params: Params, obs: jax.Array,
                            cfg: ModelConfig
                            ) -> Tuple[jax.Array, jax.Array]:
    f = conv_torso_apply(params["torso"], obs, cfg)
    logits = f @ params["pi_out"]["w"] + params["pi_out"]["b"]
    values = (f @ params["vf_out"]["w"] + params["vf_out"]["b"])[:, 0]
    return logits, values


def init_q_net_conv(key: jax.Array, cfg: ModelConfig) -> Params:
    kt, kq = jax.random.split(key)
    return {"torso": init_conv_torso(kt, cfg),
            "q_out": _init_linear(kq, conv_torso_feature_dim(cfg),
                                  cfg.num_outputs, scale=1.0)}


def q_net_conv_apply(params: Params, obs: jax.Array,
                     cfg: ModelConfig) -> jax.Array:
    f = conv_torso_apply(params["torso"], obs, cfg)
    return f @ params["q_out"]["w"] + params["q_out"]["b"]


# ------------------------------------------------- catalog dispatchers

def make_actor_critic(key: jax.Array, cfg: ModelConfig):
    """(params, apply(params, obs) -> (dist_inputs, values)) per catalog."""
    if cfg.conv_filters:
        return (init_actor_critic_conv(key, cfg),
                lambda p, obs: actor_critic_conv_apply(p, obs, cfg))
    n_hidden = len(cfg.hiddens)
    return (init_actor_critic(key, cfg),
            lambda p, obs: actor_critic_apply(p, obs, n_hidden))


def make_q_net(key: jax.Array, cfg: ModelConfig):
    """(params, apply(params, obs) -> q-values) per catalog."""
    if cfg.conv_filters:
        return (init_q_net_conv(key, cfg),
                lambda p, obs: q_net_conv_apply(p, obs, cfg))
    n_layers = len(cfg.hiddens) + 1
    return (init_q_net(key, cfg),
            lambda p, obs: q_net_apply(p, obs, n_layers))


def init_q_net(key: jax.Array, cfg: ModelConfig) -> Params:
    sizes = (cfg.obs_dim, *cfg.hiddens, cfg.num_outputs)
    keys = jax.random.split(key, len(sizes) - 1)
    return {f"q_{i}": _init_linear(k, fi, fo)
            for i, (k, fi, fo) in enumerate(zip(keys, sizes[:-1], sizes[1:]))}


def q_net_apply(params: Params, obs: jax.Array, num_layers: int) -> jax.Array:
    x = obs
    for i in range(num_layers):
        p = params[f"q_{i}"]
        x = x @ p["w"] + p["b"]
        if i < num_layers - 1:
            x = jnp.tanh(x)
    return x


# ---------------------------------------------------------------- dists

class Categorical:
    """Discrete action distribution over logits."""

    @staticmethod
    def sample(logits: jax.Array, key: jax.Array) -> jax.Array:
        return jax.random.categorical(key, logits)

    @staticmethod
    def logp(logits: jax.Array, actions: jax.Array) -> jax.Array:
        logp_all = jax.nn.log_softmax(logits)
        return jnp.take_along_axis(
            logp_all, actions[:, None].astype(jnp.int32), axis=1)[:, 0]

    @staticmethod
    def entropy(logits: jax.Array) -> jax.Array:
        logp = jax.nn.log_softmax(logits)
        return -jnp.sum(jnp.exp(logp) * logp, axis=-1)

    @staticmethod
    def kl(logits_p: jax.Array, logits_q: jax.Array) -> jax.Array:
        lp, lq = jax.nn.log_softmax(logits_p), jax.nn.log_softmax(logits_q)
        return jnp.sum(jnp.exp(lp) * (lp - lq), axis=-1)

    @staticmethod
    def deterministic(logits: jax.Array) -> jax.Array:
        return jnp.argmax(logits, axis=-1)


class DiagGaussian:
    """Continuous actions; dist_inputs = concat(mean, log_std)."""

    @staticmethod
    def _split(inputs):
        mean, log_std = jnp.split(inputs, 2, axis=-1)
        return mean, jnp.clip(log_std, -20.0, 2.0)

    @staticmethod
    def sample(inputs: jax.Array, key: jax.Array) -> jax.Array:
        mean, log_std = DiagGaussian._split(inputs)
        return mean + jnp.exp(log_std) * jax.random.normal(key, mean.shape)

    @staticmethod
    def logp(inputs: jax.Array, actions: jax.Array) -> jax.Array:
        mean, log_std = DiagGaussian._split(inputs)
        z = (actions - mean) / jnp.exp(log_std)
        return jnp.sum(-0.5 * z**2 - log_std
                       - 0.5 * jnp.log(2 * jnp.pi), axis=-1)

    @staticmethod
    def entropy(inputs: jax.Array) -> jax.Array:
        _, log_std = DiagGaussian._split(inputs)
        return jnp.sum(log_std + 0.5 * jnp.log(2 * jnp.pi * jnp.e), axis=-1)

    @staticmethod
    def kl(inputs_p: jax.Array, inputs_q: jax.Array) -> jax.Array:
        mp, lp = DiagGaussian._split(inputs_p)
        mq, lq = DiagGaussian._split(inputs_q)
        return jnp.sum(lq - lp + (jnp.exp(2 * lp) + (mp - mq) ** 2)
                       / (2 * jnp.exp(2 * lq)) - 0.5, axis=-1)

    @staticmethod
    def deterministic(inputs: jax.Array) -> jax.Array:
        mean, _ = DiagGaussian._split(inputs)
        return mean


# ------------------------------------------------- fast weight transfer

@jax.jit
def _flatten_tree(params):
    return jnp.concatenate(
        [x.reshape(-1).astype(jnp.float32)
         for x in jax.tree_util.tree_leaves(params)])


def pull_params(params) -> Dict:
    """Device→host copy of a param pytree as ONE flat transfer.

    A per-leaf ``np.asarray`` tree_map pays a full dispatch round-trip per
    leaf; one flat transfer pays it once.  Weight broadcast is on the
    learner's critical path in IMPALA, so this is the default pull
    everywhere weights move to rollout workers.

    The flat path concatenates in float32, which is only lossless when
    every leaf IS float32 — a mixed tree (int step counters, float64)
    would be silently rounded, so those trees take one
    ``jax.device_get`` of the whole tree instead (still a single batched
    host transfer)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    if not all(getattr(leaf, "dtype", None) == jnp.float32
               for leaf in leaves):
        return jax.device_get(params)
    flat = np.asarray(_flatten_tree(params))
    out, off = [], 0
    for leaf in leaves:
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        out.append(flat[off:off + n].reshape(leaf.shape))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def get_dist_class(action_space):
    if hasattr(action_space, "n"):
        return Categorical
    return DiagGaussian


def num_dist_inputs(action_space) -> int:
    if hasattr(action_space, "n"):
        return int(action_space.n)
    return 2 * int(np.prod(action_space.shape))


def flat_obs_dim(observation_space) -> int:
    return int(np.prod(observation_space.shape))
