"""One benchmark run at the program's scaled-down size, on the CPU.

It skips the harness's look for a chip (``run.chip_ready``) and drives
the rest of ``run.run``: the stamped job through ``serve_workload``, the
metrics and the check against the description's reference, whose sizes
are those of ``repro.models.scaled_down`` (float32, 2 layers, width 64,
vocabulary 128).
"""

import chip_paths  # noqa: F401  (before the modules below)
import run
import work

#: A few short requests: prompts of 8 or 16 tokens, outputs of 3 to 12.
MIX = {"prompt_len": {"median": 10, "sigma": 0.5, "min": 4, "max": 16,
                      "granule": 8},
       "gen_len": {"median": 6, "sigma": 0.5, "min": 3, "max": 12},
       "pairing_seed": 0}


#: The check's limit at this size.  The scaled-down program computes in
#: float32, as the reference does, and its served tokens read a gap of 0.0
#: on six seeds; the fp8 control in its place reads 0.056 to 0.46 on the
#: same six.
SMALL_LIMIT = 0.02


def dims(cfg: dict):
    """The sizes of the program's scaled-down model of ``cfg``, in the
    record of the model description ``cfg`` names."""
    from repro.configs import get_config
    from repro.models import scaled_down
    return work.description(cfg).program_dims(
        scaled_down(get_config(cfg["arch"])), chips=1)


def small_run(cell_name="chatglm3-6b.chat-decode", *, seed=2**31 + 11,
              requests=6, trace=False, control=False, seconds=1.0,
              open_after_s=0.0, trace_s=0.0):
    """One run of the cell's harness path."""
    bench = run.load_benchmark()
    config = run.find_cell(bench, cell_name)["config"]
    cfg = dict(work.load_config(config), mesh_shape=[1, 1])
    cell = dict(run.load_cell(cell_name))
    cell["requests_per_s"] = requests / seconds
    cell["check"] = dict(cell["check"], rows=requests, min_tokens=1,
                         limit=SMALL_LIMIT)
    cell["trace"] = {"open_after_s": open_after_s, "seconds": trace_s}
    return run.run(cell_name, seed=seed, seconds=seconds, trace=trace,
                   cfg=cfg, mix=MIX, cell=cell, bench=bench, reduced=True,
                   dims=dims(cfg),
                   peak=work.load_peaks("TPU v5 lite"), control=control,
                   log=lambda s: None)
