"""A configuration file turned into the program's ``ModelConfig``.

The file (``bench/configs/<name>.json``) holds the configuration as run
under its published key names, and under ``program`` which field of the
repo's own config each key sets.  :func:`register` builds the config from
the repo's base config with ``dataclasses.replace`` and puts it in
``repro.configs.registry.ARCHS`` under the configuration's own name, so
the program's entry (``repro.launch.serve``) finds it by ``--arch``.
"""

from __future__ import annotations

import dataclasses
import sys
from dataclasses import dataclass
from types import ModuleType
from typing import Any, Dict

from harness.spec import SpecError

#: fields that are widths: they must equal the repo's own config, which
#: must equal the published value (no width is ever cut)
WIDTHS = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "vocab")


@dataclass(frozen=True)
class Sizes:
    """What the FLOP count and the plain reference need of a dense GQA
    decoder, read from the configuration file alone."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm_eps: float
    rope_theta: float
    rope_fraction: float
    qk_norm: bool
    tie_embeddings: bool
    dtype: str                  # of the served weights


def _fields(cfg_file: Dict[str, Any]) -> Dict[str, Any]:
    prog = cfg_file["program"]
    out = {f: cfg_file["config"][k] for f, k in prog["fields"].items()}
    out.update(prog.get("fixed", {}))
    return out


def sizes(cfg_file: Dict[str, Any]) -> Sizes:
    f = _fields(cfg_file)
    return Sizes(layers=int(f["n_layers"]), d_model=int(f["d_model"]),
                 heads=int(f["n_heads"]), kv_heads=int(f["n_kv_heads"]),
                 head_dim=int(f["head_dim"]), d_ff=int(f["d_ff"]),
                 vocab=int(f["vocab"]), norm_eps=float(f["norm_eps"]),
                 rope_theta=float(f["rope_theta"]),
                 rope_fraction=float(f["rope_fraction"]),
                 qk_norm=bool(f["qk_norm"]),
                 tie_embeddings=bool(f["tie_embeddings"]), dtype=f["dtype"])


def _replace(base, fields: Dict[str, Any], name: str):
    from repro.models.common import default_blocks
    kw = {}
    for k, v in fields.items():
        cur = getattr(base, k)
        kw[k] = float(v) if isinstance(cur, float) else v
    n = kw.get("n_layers", base.n_layers)
    return dataclasses.replace(base, name=name, blocks=default_blocks(n), **kw)


def model_config(name: str, cfg_file: Dict[str, Any], smoke: bool = False):
    """The ``ModelConfig`` the cell runs.  ``smoke`` keeps the repo's
    CPU-sized smoke widths and depth, with the file's other settings."""
    from repro.configs import get_config, get_smoke_config
    base_name = cfg_file["program"]["base"]
    fields = _fields(cfg_file)
    base = get_config(base_name)
    for w in WIDTHS:
        if getattr(base, w) != fields[w]:
            raise SpecError(f"{name}: {w} is {fields[w]} in the file and "
                            f"{getattr(base, w)} in the repo's {base_name}")
    if base.family != "dense" or base.moe is not None:
        raise SpecError(f"{name}: only dense decoders are described here")
    if smoke:
        small = get_smoke_config(base_name)
        keep = ("n_layers",) + WIDTHS
        return _replace(small, {k: v for k, v in fields.items()
                                if k not in keep}, name)
    cfg = _replace(base, fields, name)
    cfg.validate()
    return cfg


def smoke_sizes(name: str, cfg_file: Dict[str, Any]) -> Sizes:
    """:class:`Sizes` of the smoke-sized config (CPU rehearsals only)."""
    c = model_config(name, cfg_file, smoke=True)
    return Sizes(layers=c.n_layers, d_model=c.d_model, heads=c.n_heads,
                 kv_heads=c.n_kv_heads, head_dim=c.dh, d_ff=c.d_ff,
                 vocab=c.vocab, norm_eps=c.norm_eps, rope_theta=c.rope_theta,
                 rope_fraction=c.rope_fraction, qk_norm=c.qk_norm,
                 tie_embeddings=c.tie_embeddings, dtype=c.dtype)


def register(name: str, cfg_file: Dict[str, Any]) -> None:
    """Make ``--arch <name>`` resolve to this configuration."""
    from repro.configs.registry import ARCHS
    mod_name = "bench_config_" + "".join(
        c if c.isalnum() else "_" for c in name)
    mod = ModuleType(mod_name)
    mod.config = lambda: model_config(name, cfg_file)
    mod.smoke_config = lambda: model_config(name, cfg_file, smoke=True)
    sys.modules[mod_name] = mod
    ARCHS[name] = mod_name
