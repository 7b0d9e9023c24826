"""The program's model configs for a configuration file's models."""
from __future__ import annotations

from typing import Dict, List


def program_matches(cfg, sizes: Dict) -> List[str]:
    """The sizes of ``sizes`` that the program's config ``cfg`` does not
    have."""
    return [f"{k}: program {getattr(cfg, k)!r}, file {v!r}"
            for k, v in sizes.items()
            if hasattr(cfg, k) and getattr(cfg, k) != v]


def program_config(config: Dict, side: str, log=None):
    """The program's registered config of ``config[side]["program"]`` with
    the file's sizes and dtype. A difference from the registered config is
    logged: it is a cut that the file's ``reduced`` lists."""
    from repro.configs import get_config
    sizes = dict(config[side])
    cfg = get_config(sizes.pop("program"))
    sizes["dtype"] = config["dtype"]
    changed = program_matches(cfg, sizes)
    if changed and log is not None:
        log(f"config {cfg.name} run with the file's sizes: {changed}")
    return cfg.scaled(**sizes)


def program_configs(config: Dict, log=None):
    """(source, target) configs of a growth pair."""
    return [program_config(config, side, log) for side in ("src", "dst")]
