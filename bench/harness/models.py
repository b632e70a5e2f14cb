"""A configuration file turned into the program's ``ModelConfig``.

The file (``bench/configs/<name>.json``) holds the configuration as run
under its published key names, and under ``program`` which field of the
repo's own config each key sets and which family (``bench/families/
<family>.py``, see :func:`harness.spec.family`) describes the model.  The
family builds the config with :func:`build`; :func:`register` puts it in
``repro.configs.registry.ARCHS`` under the configuration's own name, so the
program's entry (``repro.launch.serve``) finds it by ``--arch``.
"""

from __future__ import annotations

import dataclasses
import sys
from types import ModuleType
from typing import Any, Dict, Sequence

from harness.spec import SpecError


def fields(cfg_file: Dict[str, Any]) -> Dict[str, Any]:
    """The fields of the repo's config that the file sets, by their names."""
    prog = cfg_file["program"]
    out = {f: cfg_file["config"][k] for f, k in prog["fields"].items()}
    out.update(prog.get("fixed", {}))
    return out


def _replace(base, values: Dict[str, Any], name: str):
    from repro.models.common import default_blocks
    kw = {}
    for k, v in values.items():
        cur = getattr(base, k)
        kw[k] = float(v) if isinstance(cur, float) else v
    n = kw.get("n_layers", base.n_layers)
    return dataclasses.replace(base, name=name, blocks=default_blocks(n), **kw)


def build(name: str, cfg_file: Dict[str, Any], widths: Sequence[str],
          smoke: bool = False):
    """The repo's base config (``program.base``) with the file's fields set.
    Each field in ``widths`` must already equal the base's: no width is
    ever cut.  ``smoke`` keeps the repo's CPU-sized smoke widths and depth,
    with the file's other settings."""
    from repro.configs import get_config, get_smoke_config
    base_name = cfg_file["program"]["base"]
    f = fields(cfg_file)
    base = get_config(base_name)
    for w in widths:
        if getattr(base, w) != f[w]:
            raise SpecError(f"{name}: {w} is {f[w]} in the file and "
                            f"{getattr(base, w)} in the repo's {base_name}")
    if smoke:
        small = get_smoke_config(base_name)
        keep = ("n_layers",) + tuple(widths)
        return _replace(small, {k: v for k, v in f.items() if k not in keep},
                        name)
    cfg = _replace(base, f, name)
    cfg.validate()
    return cfg


def register(name: str, cfg_file: Dict[str, Any], family: ModuleType) -> None:
    """Make ``--arch <name>`` resolve to this configuration, as ``family``
    builds it."""
    from repro.configs.registry import ARCHS
    mod_name = "bench_config_" + "".join(
        c if c.isalnum() else "_" for c in name)
    mod = ModuleType(mod_name)
    mod.config = lambda: family.program_config(name, cfg_file)
    mod.smoke_config = lambda: family.program_config(name, cfg_file,
                                                     smoke=True)
    sys.modules[mod_name] = mod
    ARCHS[name] = mod_name
