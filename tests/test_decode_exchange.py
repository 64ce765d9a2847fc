"""The engine's count of the bytes a decode step exchanges between devices
(``ServeMetrics.decode_exchange_bytes``): the scaled-down granite-3-8b
served over a (1, 4) mesh of four virtual CPU devices reads within a factor
of two of the Megatron count at that size, two all-reduces of the batch's
hidden states a layer and one for the embedding; over (1, 1) it reads 0."""

import json


def test_decode_exchange_bytes_over_four_devices_and_one():
    from conftest import run_py
    r = run_py("""
import json
from repro.configs import get_config
from repro.models import scaled_down
from repro.obs import Tracer
from repro.serve import ServeConfig, WorkloadSpec, serve_workload

cfg = scaled_down(get_config("granite-3-8b"))
out = {"layers": cfg.num_layers, "d": cfg.d_model, "dtype": cfg.dtype}
for mesh in ((1, 4), (1, 1)):
    # Prompts and outputs that fill 24 cache slots: the cache is split over
    # the devices by slot.
    spec = WorkloadSpec(num_requests=6, seed=3, prompt_lens=(8, 16),
                        gen_lens=(3, 8))
    tracer = Tracer()
    res = serve_workload(spec, config=ServeConfig(
        arch="granite-3-8b", reduced=True, execute=True, fabric="wallclock",
        max_batch=4, mesh_shape=mesh, tracer=tracer))
    counters = [e.args["value"] for e in tracer.events
                if e.ph == "C" and e.name == "decode_exchange_bytes"]
    out[str(mesh[1])] = {"bytes": res["metrics"].decode_exchange_bytes,
                         "counters": counters}
print(json.dumps(out))
""", devices=4)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["dtype"] == "float32"
    megatron = (2 * out["layers"] + 1) * 4 * out["d"] * 4
    tp = out["4"]
    assert megatron / 2 <= tp["bytes"] <= 2 * megatron
    assert tp["counters"] == [tp["bytes"]]
    assert out["1"] == {"bytes": 0, "counters": []}
