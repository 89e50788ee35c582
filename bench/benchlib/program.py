"""The system under test, as a configuration file asks for it.

The only module of the benchmark that reads the program's configuration
classes: it builds the program's ``ModelConfig`` from a configuration
file and refuses a file that the program would not run as written, or
whose reference does not model what the program would run.

A key of the file that names a field of ``ModelConfig`` sets that
field. Where the field is itself a configuration (``moe``, ``mla``,
...), the key holds a dict of that configuration's fields. Every such
key, nested ones included, must come out as the file says; other keys
(the source, what was assumed) are the file's own.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

from benchlib import spec


def model_config(cfg: Dict[str, Any]):
    from repro.configs import base as cfgbase

    base = cfgbase.resolve(cfg["program_arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    over: Dict[str, Any] = {}
    want: Dict[str, Any] = {}      # dotted field name -> the file's value
    wrong: Dict[str, Any] = {}
    for k in sorted(fields & set(cfg)):
        sub = getattr(base, k)
        if not dataclasses.is_dataclass(sub):
            over[k] = want[k] = cfg[k]
        elif not isinstance(cfg[k], dict):
            wrong[k] = f"{cfg[k]!r} is not a dict of {type(sub).__name__}"
        else:
            known = {f.name for f in dataclasses.fields(sub)}
            for kk in sorted(set(cfg[k]) - known):
                wrong[f"{k}.{kk}"] = f"not a field of {type(sub).__name__}"
            kept = {kk: v for kk, v in cfg[k].items() if kk in known}
            over[k] = dataclasses.replace(sub, **kept)
            want.update({f"{k}.{kk}": v for kk, v in kept.items()})
    mc = dataclasses.replace(base, **over)
    for name, v in want.items():
        got = functools.reduce(getattr, name.split("."), mc)
        if got != v:
            wrong[name] = got
    if wrong:
        raise SystemExit(f"bench: the program would run {cfg['name']} "
                         f"with {wrong}, not as its file says")
    missing = spec.reference(cfg).unmodelled(mc)
    if missing:
        raise SystemExit(f"bench: {cfg['reference']} does not model "
                         f"{', '.join(missing)}, which the program would "
                         f"run for {cfg['name']}")
    return mc


def seed31(seed: int) -> int:
    """The program's and the reference's PRNG seed for a run seed of
    any size (a run seed may exceed 32 bits)."""
    return int(seed) % (2 ** 31 - 1)
