"""Roofline serving-cost model: per-token decode / prefill time in µs.

Bridges the repo's two halves: the JAX serving stack knows what a model
costs per token (a roofline over ``configs/registry.py`` architectures),
and the consensus plane now has a deferred execution engine
(``App.cost_us``) that charges deterministic service time per decided
request.  This module turns an architecture
into that charge.

The decode roofline (one token for each of B batched streams):

    t_step = max( 2·N_active·B / PEAK_FLOPS,
                  (param_bytes + B·kv_bytes·ctx) / HBM_BW )

Small-batch decode is HBM-bound on reading the weights, so per-token
cost ≈ param_bytes / (HBM_BW·B) — the classic batching amortization.
Prefill is charged as one compute-bound pass over the prompt, amortized
across the same serving batch.  Constants are one TPU v5e's
(197 TFLOP/s bf16, 819 GB/s HBM), as in ``bench/harness/peaks.json``.

``from_arch`` derives the parameter/KV byte counts analytically from a
:class:`repro.models.common.ModelConfig` (attention stacks with dense or
MoE FFNs — the gemma3/llama4/qwen3 serving archetypes); it imports the
config registry lazily because ``models/common.py`` imports JAX at
module level.  ``from_counts`` takes the counts directly and needs only
numpy — benchmarks and the fast test tier use it.
"""

from __future__ import annotations

from dataclasses import dataclass

# one TPU v5e (a copy of bench/harness/peaks.json)
PEAK_FLOPS = 197e12
HBM_BW = 819e9


@dataclass(frozen=True)
class ServingCostModel:
    name: str
    param_bytes: float           # HBM-resident weight bytes
    active_params: float         # params touched per token (MoE: top-k only)
    kv_bytes_per_token: float    # KV-cache bytes appended per token, all layers
    batch: int = 32              # serving batch size B (streams per step)
    peak_flops: float = PEAK_FLOPS
    hbm_bw: float = HBM_BW

    # ------------------------------------------------------------ decode
    def decode_step_us(self, ctx: int = 0) -> float:
        """One batched decode step (B tokens), roofline max of compute
        and memory terms, in µs.  ``ctx`` is the per-stream context."""
        t_compute = 2.0 * self.active_params * self.batch / self.peak_flops
        t_memory = (self.param_bytes +
                    self.batch * self.kv_bytes_per_token * ctx) / self.hbm_bw
        return 1e6 * max(t_compute, t_memory)

    def decode_us_per_token(self, ctx: int = 0) -> float:
        """Per-request share of one decode step."""
        return self.decode_step_us(ctx) / self.batch

    # ----------------------------------------------------------- prefill
    def prefill_us(self, n_prompt: int) -> float:
        """One prompt pass (compute-bound at length, memory-bound floor
        of one weight read), amortized across the serving batch."""
        t_compute = 2.0 * self.active_params * n_prompt / self.peak_flops
        t_memory = self.param_bytes / self.hbm_bw
        return 1e6 * max(t_compute, t_memory) / self.batch

    def request_us(self, n_prompt: int, n_decode: int, ctx: int = 0) -> float:
        """Total service time of one request: prefill the prompt, then
        decode ``n_decode`` tokens at context ``ctx + n_prompt``."""
        return (self.prefill_us(n_prompt) +
                n_decode * self.decode_us_per_token(ctx + n_prompt))

    # ------------------------------------------------------ constructors
    @classmethod
    def from_counts(cls, name: str, n_params: float,
                    kv_bytes_per_token: float,
                    n_active: float = 0.0, batch: int = 32,
                    dtype_bytes: int = 2) -> "ServingCostModel":
        return cls(name=name, param_bytes=n_params * dtype_bytes,
                   active_params=n_active or n_params,
                   kv_bytes_per_token=kv_bytes_per_token, batch=batch)

    @classmethod
    def from_arch(cls, arch: str, batch: int = 32,
                  dtype_bytes: int = 2) -> "ServingCostModel":
        """Analytic counts from the architecture registry (imports the
        JAX-backed model configs — slow path / slow test tier only)."""
        from repro.configs.registry import get_config
        cfg = get_config(arch)
        D, dh = cfg.d_model, cfg.dh
        H, KV = cfg.n_heads, cfg.n_kv_heads
        n_total = float(cfg.vocab * D)            # embed
        if not cfg.tie_embeddings:
            n_total += cfg.vocab * D              # lm_head
        n_total += D                              # out_norm
        n_moe_inactive = 0.0
        kv_bytes = 0.0
        for spec in cfg.layer_list():
            if spec.kind != "attn":
                raise ValueError(
                    f"{arch}: serving cost model covers attention stacks "
                    f"(got layer kind {spec.kind!r})")
            n_total += D                          # ln1
            n_total += D * H * dh + 2 * D * KV * dh + H * dh * D
            if cfg.qk_norm:
                n_total += 2 * dh
            kv_bytes += 2.0 * KV * dh * dtype_bytes
            if spec.has_ffn:
                n_total += D                      # ln2
                if cfg.moe is not None:
                    m = cfg.moe
                    expert = 3.0 * D * m.d_expert
                    n_total += D * m.n_experts + m.n_experts * expert
                    n_moe_inactive += expert * (m.n_experts - m.top_k)
                else:
                    n_total += 3.0 * D * cfg.d_ff
        return cls(name=arch, param_bytes=n_total * dtype_bytes,
                   active_params=n_total - n_moe_inactive,
                   kv_bytes_per_token=kv_bytes, batch=batch)
