"""The training step the launcher jits: fwd + bwd + AdamW + the uBFT
attestation fingerprints of the gradients and the new parameters.

  make_train_step(cfg, ctx, opt_cfg)
"""

from __future__ import annotations

from typing import Optional

import jax

from repro.models.common import ModelConfig
from repro.models.transformer import ShardCtx, lm_loss
from repro.optim.adamw import AdamWConfig, adamw_update
from repro.runtime.attest import fingerprint_tree


def make_train_step(cfg: ModelConfig, ctx: Optional[ShardCtx] = None,
                    opt_cfg: Optional[AdamWConfig] = None):
    opt_cfg = opt_cfg or AdamWConfig()

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: lm_loss(cfg, p, batch["inputs"], batch["targets"], ctx)
        )(params)
        new_params, new_opt = adamw_update(params, grads, opt_state, opt_cfg)
        # uBFT attestation: replicas CTBcast these (see repro.runtime.trainer)
        metrics = {"loss": loss,
                   "grad_fp": fingerprint_tree(grads),
                   "param_fp": fingerprint_tree(new_params)}
        return new_params, new_opt, metrics

    return train_step
