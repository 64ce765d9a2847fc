"""Record the small device trace that tests/test_reduce.py reads.

    python benchmarks/chip/tests/record_trace.py OUT_DIR

On a TPU machine: runs a jitted matrix product three times, and on more
than one chip also an all-reduce over all of them, under the profiler, and
writes the ``.xplane.pb`` to OUT_DIR together with ``summary.json``, which
lists each plane's lines and their first event names.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    if jax.default_backend() != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2

    @jax.jit
    def small_matmul(x):
        return (x @ x).sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    small_matmul(x).block_until_ready()
    devs = jax.devices()
    reduce_fn = None
    if len(devs) > 1:
        mesh = Mesh(np.array(devs), ("model",))
        sh = NamedSharding(mesh, P("model"))
        y = jax.device_put(jnp.ones((len(devs) * 256, 256), jnp.float32), sh)

        @jax.jit
        def small_allreduce(y):
            return jax.lax.with_sharding_constraint(
                y.sum(axis=0), NamedSharding(mesh, P()))

        reduce_fn = small_allreduce
        reduce_fn(y).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out / "profile"), profiler_options=opts)
    for _ in range(3):
        small_matmul(x).block_until_ready()
        if reduce_fn is not None:
            reduce_fn(y).block_until_ready()
    jax.profiler.stop_trace()

    from jax.profiler import ProfileData
    files = sorted((out / "profile").rglob("*.xplane.pb"))
    data = ProfileData.from_file(str(files[-1]))
    summary = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"line": line.name, "events": len(evs),
                          "names": sorted({e.name for e in evs})[:40]})
        summary.append({"plane": plane.name, "lines": lines})
    (out / "summary.json").write_text(json.dumps(summary, indent=1))
    print(f"record_trace: {files[-1]} ({files[-1].stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
