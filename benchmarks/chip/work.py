"""What the work counts share: configurations, their model descriptions,
the peak table and the roofline.

A configuration file under ``benchmarks/chip/configs`` names its model
description by ``"description"``: the module ``models/<name>.py``, found
by name as ``metrics/<metric>.py`` is.  The description turns the file's
published keys into sizes and counts the work from them (parameter and
cache bytes, model FLOPs), never from the program, so the yardstick stays
put while the program changes.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent

#: Bytes per element of the serving dtypes.
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def description(cfg: dict):
    """The model description module ``cfg`` names (loaded once per
    process); a missing or unknown name is an error that names the files."""
    where = f"benchmarks/chip/configs/{cfg.get('name')}.json"
    name = cfg.get("description")
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"{where} names no model description: its "
                         f"'description' is {name!r}")
    path = HERE / "models" / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"{where} names the model description {name!r}, "
                         f"and there is no benchmarks/chip/models/{name}.py")
    key = f"chip_model_{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return sys.modules[key]


def decode_live_positions(prompt_lens, gen_lens) -> int:
    """Cache positions read over all decode steps of a job, summed."""
    total = 0
    for p, g in zip(prompt_lens, gen_lens):
        steps = g - 1
        total += steps * p + steps * (steps + 1) // 2
    return total


def roofline_s(flops: float, nbytes: float, peak: dict,
               chips: int = 1) -> tuple[float, str]:
    """Least seconds one chip needs for its share: the larger of FLOPs over
    peak FLOP/s and bytes over peak bandwidth, and which one bounds it.
    ``flops`` is the whole step's; ``nbytes`` is already per chip."""
    t_f = flops / chips / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")


def load_peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table['devices'])})"
                       ) from None
