"""Model substrate: configs, parameter initialization, shared layers.

Every assigned architecture is described by a :class:`ModelConfig` whose
``blocks`` field is a *pattern program*: a list of (pattern, repeats) groups,
where a pattern is a tuple of :class:`LayerSpec`s.  The forward pass scans
over ``repeats`` within each group (one compiled body per group), which keeps
HLO size O(#distinct layer kinds) instead of O(#layers) — essential for the
512-device dry-run compiles — while supporting heterogeneous stacks
(gemma3's 5:1 local:global, recurrentgemma's 1:2 RG-LRU:attention,
xLSTM's mLSTM/sLSTM alternation).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Layer / model configuration
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class LayerSpec:
    """One layer in the pattern program."""
    kind: str                   # "attn" | "mlstm" | "slstm" | "rglru"
    window: Optional[int] = None   # attention window (None = full/causal)
    has_ffn: bool = True           # xLSTM blocks carry their own projections


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int              # routed experts: the router's width
    top_k: int
    d_expert: int
    capacity_factor: float = 1.25   # mesh path only (repro.models.moe)
    router_dtype: str = "float32"
    #: (first, count): the experts this device holds, one expert-parallel
    #: share of the layer; None holds all ``n_experts``
    held: Optional[Tuple[int, int]] = None

    @property
    def held_range(self) -> Tuple[int, int]:
        return self.held or (0, self.n_experts)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    blocks: Tuple[Tuple[Tuple[LayerSpec, ...], int], ...] = ()
    moe: Optional[MoEConfig] = None
    rope_theta: float = 10_000.0
    rope_fraction: float = 1.0   # chatglm3: rotary on half the head dim ("2d")
    qk_norm: bool = False        # qwen3
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    dtype: str = "bfloat16"
    # modality frontend stub: inputs are precomputed embeddings, not token ids
    frontend: Optional[str] = None   # None | "audio" | "vlm"
    max_seq: int = 131_072
    # --- runtime / performance knobs (hillclimbed in §Perf) ---
    remat: str = "full"          # "none" | "dots" | "full"
    q_chunk: int = 512
    kv_chunk: int = 1024
    mlstm_chunk: int = 256
    logits_fp32: bool = False
    attest: bool = True          # fingerprint grads/params each step (uBFT)
    fsdp_gather: bool = False    # ZeRO-3 per-layer weight gather (§Perf #1)
    attn_head_shard: bool = False  # expand KV to H heads + shard heads (§Perf #2)

    @property
    def dh(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def jdtype(self):
        return jnp.dtype(self.dtype)

    def layer_list(self) -> List[LayerSpec]:
        out: List[LayerSpec] = []
        for pattern, reps in self.blocks:
            out.extend(list(pattern) * reps)
        return out

    def validate(self) -> None:
        assert len(self.layer_list()) == self.n_layers, (
            f"{self.name}: pattern program has {len(self.layer_list())} "
            f"layers, config says {self.n_layers}")


def default_blocks(n_layers: int) -> Tuple:
    """Uniform full-attention stack."""
    return (((LayerSpec("attn"),), n_layers),)


def params_count(tree: Any) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Shared primitives
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         fraction: float = 1.0) -> jax.Array:
    """Rotary embedding over the leading ``fraction`` of the head dim.

    x: (..., S, H, dh); positions: (..., S) int32.
    chatglm3's "RoPE 2d" applies rotary to half the dimensions.
    """
    dh = x.shape[-1]
    rot = int(dh * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    ang = ang[..., None, :]                                  # (..., S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------
def _init(key, shape, scale, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_layer_params(cfg: ModelConfig, spec: LayerSpec, key) -> Dict[str, Any]:
    """Parameters for one layer of the given kind (unstacked)."""
    D, dh = cfg.d_model, cfg.dh
    H, KV = cfg.n_heads, cfg.n_kv_heads
    dt = cfg.jdtype()
    ks = jax.random.split(key, 16)
    s_in = D ** -0.5
    p: Dict[str, Any] = {"ln1": jnp.zeros((D,), dt)}

    if spec.kind == "attn":
        p["wq"] = _init(ks[0], (D, H * dh), s_in, dt)
        p["wk"] = _init(ks[1], (D, KV * dh), s_in, dt)
        p["wv"] = _init(ks[2], (D, KV * dh), s_in, dt)
        p["wo"] = _init(ks[3], (H * dh, D), (H * dh) ** -0.5, dt)
        if cfg.qk_norm:
            p["q_norm"] = jnp.zeros((dh,), dt)
            p["k_norm"] = jnp.zeros((dh,), dt)
    elif spec.kind == "mlstm":
        # matrix-LSTM: q/k/v + input/forget gates, per-head matrix memory
        p["wq"] = _init(ks[0], (D, H * dh), s_in, dt)
        p["wk"] = _init(ks[1], (D, H * dh), s_in, dt)
        p["wv"] = _init(ks[2], (D, H * dh), s_in, dt)
        p["wi"] = _init(ks[4], (D, H), s_in, dt)
        p["wf"] = _init(ks[5], (D, H), s_in, dt)
        p["bf"] = jnp.full((H,), 3.0, dt)   # forget bias: remember by default
        p["wo"] = _init(ks[3], (H * dh, D), (H * dh) ** -0.5, dt)
        p["up"] = _init(ks[6], (D, 2 * D), s_in, dt)   # block up-projection
        p["down"] = _init(ks[7], (D, D), D ** -0.5, dt)
    elif spec.kind == "slstm":
        # scalar-LSTM with exponential gating (recurrent weights diagonal-
        # block approximated by per-head dense)
        p["wz"] = _init(ks[0], (D, D), s_in, dt)
        p["wi"] = _init(ks[1], (D, D), s_in, dt)
        p["wf"] = _init(ks[2], (D, D), s_in, dt)
        p["wo_gate"] = _init(ks[4], (D, D), s_in, dt)
        p["rz"] = _init(ks[5], (cfg.n_heads, cfg.d_model // cfg.n_heads,
                                cfg.d_model // cfg.n_heads), s_in, dt)
        p["wo"] = _init(ks[3], (D, D), D ** -0.5, dt)
        p["up"] = _init(ks[6], (D, 2 * D), s_in, dt)
        p["down"] = _init(ks[7], (D, D), D ** -0.5, dt)
    elif spec.kind == "rglru":
        # RG-LRU (RecurrentGemma): conv1d + gated linear recurrence at
        # lru_width = d_model; the MLP lives in the shared FFN part below
        W = D
        p["w_in"] = _init(ks[0], (D, 2 * W), s_in, dt)   # x and gate
        p["conv"] = _init(ks[1], (4, W), 0.1, dt)
        p["wa"] = _init(ks[2], (W, W), W ** -0.5, dt)
        p["wx"] = _init(ks[4], (W, W), W ** -0.5, dt)
        p["lam"] = _init(ks[5], (W,), 1.0, jnp.float32)  # recurrence gate param
        p["w_out"] = _init(ks[3], (W, D), W ** -0.5, dt)
    else:
        raise ValueError(spec.kind)

    if spec.has_ffn and spec.kind in ("attn", "rglru"):
        p["ln2"] = jnp.zeros((D,), dt)
        if cfg.moe is not None:
            m = cfg.moe
            F = m.d_expert
            p["router"] = _init(ks[8], (D, m.n_experts), s_in, jnp.float32)
            # only the held experts, each from its own key (its index folded
            # into the matrix's), so a share's experts are a slice of the
            # whole layer's
            e0, n = m.held_range
            experts = jnp.arange(e0, e0 + n)

            def draw(k, shape, scale):
                keys = jax.vmap(lambda e: jax.random.fold_in(k, e))(experts)
                return jax.vmap(lambda ke: _init(ke, shape, scale, dt))(keys)
            p["w_gate"] = draw(ks[9], (D, F), s_in)
            p["w_up"] = draw(ks[10], (D, F), s_in)
            p["w_down"] = draw(ks[11], (F, D), F ** -0.5)
        else:
            p["w_gate"] = _init(ks[9], (D, cfg.d_ff), s_in, dt)
            p["w_up"] = _init(ks[10], (D, cfg.d_ff), s_in, dt)
            p["w_down"] = _init(ks[11], (cfg.d_ff, D), cfg.d_ff ** -0.5, dt)
    return p


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    """Full parameter pytree with per-group stacked layer params."""
    cfg.validate()
    dt = cfg.jdtype()
    k_emb, k_out, k_layers = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embed": _init(k_emb, (cfg.vocab, cfg.d_model), cfg.d_model ** -0.5, dt),
        "out_norm": jnp.zeros((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _init(k_out, (cfg.d_model, cfg.vocab),
                                  cfg.d_model ** -0.5, dt)
    groups = []
    kg = k_layers
    for gi, (pattern, reps) in enumerate(cfg.blocks):
        kg, kp = jax.random.split(kg)
        # stack `reps` copies of each pattern position
        stacked = []
        for li, spec in enumerate(pattern):
            keys = jax.random.split(jax.random.fold_in(kp, li), reps)
            per = [init_layer_params(cfg, spec, keys[r]) for r in range(reps)]
            stacked.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per))
        groups.append(tuple(stacked))
    params["groups"] = tuple(groups)
    return params
