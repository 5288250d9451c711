"""The program's configuration, built from a configuration file.

The file holds the published sizes under the source's own keys; the
program's ``ModelConfig`` is the registered architecture (``program_arch``)
with those sizes, the family's own fields (``program_fields`` of
``reference/<family>.py``) and ``program_overrides`` put in. Each size that
the family's ``KEYS`` maps is checked against the config the program will
run, so a file and the program cannot drift apart unseen.
"""
from __future__ import annotations

import dataclasses
import pathlib
from typing import Any, Dict

import bench


def model_config(conf: Dict[str, Any], base: pathlib.Path = bench.HERE):
    """The program's ModelConfig for a configuration file."""
    from repro.config import get_arch

    fam = bench.family(conf, base)
    arch = get_arch(conf["program_arch"])
    kw = {field: conf[k] for k, field in fam.KEYS.items() if k in conf}
    kw.update(fam.program_fields(conf, arch))
    kw.update(conf.get("program_overrides", {}))
    cfg = dataclasses.replace(arch, **kw)
    for k, field in fam.KEYS.items():
        if k in conf and getattr(cfg, field) != conf[k]:
            raise SystemExit(f"bench: {conf['name']}: {k}={conf[k]} but the program "
                             f"runs {field}={getattr(cfg, field)}")
    return cfg
