"""Measured (not simulated) dispatch + sync scaling on the host-CPU fabric.

The paper's claim, re-validated on real hardware at the JAX dispatch layer:
sequential per-device placement costs grow linearly with the device count
while one multicast placement stays ~flat; completion detection via the
credit counter is one host interaction vs one per device for polling.

Runs in a subprocess with N virtual host devices, pinned to the CPU backend
(``JAX_PLATFORMS=cpu``): the children never reach for an accelerator the
parent may hold, and every number they print is a host-CPU number. Fits
the measured times to the paper's model form t(M) = alpha + delta*M and
reports the fit + MAPE.

Prints CSV: devices,seq_put_us,mc_put_us,poll_wait_us,credit_wait_us
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_CHILD = r"""
import json, time
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.dispatch import MulticastDispatcher, SequentialDispatcher
from repro.core.sync import CreditCounterSync, PollingSync, attach_credits
from repro.launch.mesh import make_mesh

devs = len(jax.devices())
mesh = make_mesh((devs,), ("data",))
x = np.ones((256, 1024), np.float32)          # 1 MiB operand
sh = NamedSharding(mesh, P())                 # replicated: multicast target
mc, sq = MulticastDispatcher(), SequentialDispatcher()
REPS = 30

def best(fn):
    ts = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return sum(ts[:10]) / 10

mc.put(x, sh); sq.put(x, sh)                  # warmup
t_mc = best(lambda: jax.block_until_ready(mc.put(x, sh)))
t_sq = best(lambda: jax.block_until_ready(sq.put(x, sh)))

step = jax.jit(attach_credits(lambda v: {"y": v * 2.0}, mesh),
               in_shardings=NamedSharding(mesh, P("data")))
xb = jnp.ones((devs * 128, 64), jnp.float32)
out, credits = step(xb)
jax.block_until_ready((out, credits))
cc, pl = CreditCounterSync(mesh), PollingSync(mesh)

def run_credit():
    o, c = step(xb); cc.wait(c)
def run_poll():
    o, c = step(xb); pl.wait(o)
t_credit = best(run_credit)
t_poll = best(run_poll)
print(json.dumps(dict(devices=devs, platform=jax.devices()[0].platform,
                      seq_put_s=t_sq, mc_put_s=t_mc,
                      poll_s=t_poll, credit_s=t_credit)))
"""


def measure(devices: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=300)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def main():
    from repro.core import runtime_model as rm
    rows = [measure(d) for d in (1, 2, 4, 8)]
    print(f"# platform: {rows[0]['platform']} (host virtual devices)")
    print("devices,seq_put_us,mc_put_us,poll_wait_us,credit_wait_us")
    for r in rows:
        print(f"{r['devices']},{r['seq_put_s']*1e6:.0f},"
              f"{r['mc_put_s']*1e6:.0f},{r['poll_s']*1e6:.0f},"
              f"{r['credit_s']*1e6:.0f}")
    # Fit the baseline dispatch to the paper's linear model t = a + d*M.
    import numpy as np
    m = np.array([r["devices"] for r in rows], float)
    t = np.array([r["seq_put_s"] for r in rows], float)
    a_fit = np.vstack([np.ones_like(m), m]).T
    coef, *_ = np.linalg.lstsq(a_fit, t, rcond=None)
    pred = a_fit @ coef
    mape = 100 * float(np.mean(np.abs(pred - t) / t))
    print(f"# sequential fit: t = {coef[0]*1e6:.0f}us + {coef[1]*1e6:.0f}us"
          f"*M  (MAPE {mape:.1f}%)")
    slope_ratio = (rows[-1]["mc_put_s"] - rows[0]["mc_put_s"]) / \
        max(rows[-1]["seq_put_s"] - rows[0]["seq_put_s"], 1e-12)
    print(f"# multicast slope / sequential slope = {slope_ratio:.2f} "
          f"(paper: ~0 — dispatch cost constant in M)")


if __name__ == "__main__":
    main()
