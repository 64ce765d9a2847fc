"""The one traffic generator, and the requests that stamp real time.

A traffic mix is a data file under ``benchmarks/chip/traffic``; this module
turns it, a request count and a seed into the job.  The job is a backlog:
every request is queued at time 0, as an offline batch job submits it.

Each of ``prompt_len`` and ``gen_len`` is a log-normal distribution given
by its ``median`` and ``sigma`` (of the log), cut to ``[min, max]`` and
rounded to the nearest multiple of ``granule`` (the program compiles
one prefill program per prompt length).  The job's ``n`` lengths are the
distribution's quantiles at ``(i + 0.5) / n``, so every seed gets the same
set of lengths; prompts and outputs are paired by a fixed permutation
drawn from the file's ``pairing_seed``.  ``--seed`` draws the prompt
tokens and the order in which the job is submitted (its arrival order),
unless the file fixes the order by an ``order_seed``: where the order
decides how many prefills the program runs, a seed's order would change
the work.  Requests carry no deadline.

The serving loop timestamps a request on its virtual fabric clock.
:class:`StampedRequest` records ``time.perf_counter()`` as well, each time
the loop sets ``t_admitted``, ``t_first_token`` or ``t_done``, and calls a
hook with it, so the harness measures on the real clock without touching
the program.
"""

from __future__ import annotations

import dataclasses
import json
import math
import time
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent

#: Request fields whose assignment is stamped on the host clock.
STAMPED = ("t_admitted", "t_first_token", "t_done")


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _cut(x: float, dist: dict) -> int:
    """``x`` cut to ``[min, max]`` and rounded to the nearest multiple of
    ``granule``."""
    granule = int(dist.get("granule", 1))
    x = min(max(x, dist["min"]), dist["max"])
    return granule * max(1, math.floor(x / granule + 0.5))


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """The ``n`` lengths of a log-normal at quantiles ``(i + 0.5) / n``,
    each cut by ``_cut``, in ascending order."""
    normal = NormalDist()
    mu, sigma = math.log(dist["median"]), float(dist["sigma"])
    return np.asarray(
        [_cut(math.exp(mu + sigma * normal.inv_cdf((i + 0.5) / n)), dist)
         for i in range(n)], np.int64)


def longest(mix: dict) -> tuple[int, int]:
    """The longest prompt and output any job of the mix can hold."""
    return (_cut(mix["prompt_len"]["max"], mix["prompt_len"]),
            _cut(mix["gen_len"]["max"], mix["gen_len"]))


def lengths(mix: dict, n: int) -> tuple[np.ndarray, np.ndarray]:
    """(prompt_lens, gen_lens) of the ``n`` requests, paired but not yet
    in the order of any seed."""
    prompts = quantile_lengths(mix["prompt_len"], n)
    gens = quantile_lengths(mix["gen_len"], n)
    rng = np.random.default_rng([int(mix["pairing_seed"]), 0x7A1])
    return prompts[rng.permutation(n)], gens


def build_requests(mix: dict, n: int, seed: int, vocab: int, request_cls):
    """The job: ``n`` requests of ``request_cls``, in the seed's order (or
    the file's), with the seed's prompt tokens."""
    prompts, gens = lengths(mix, n)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                 int(seed) >> 32, 0x70C])
    order_rng = (np.random.default_rng([int(mix["order_seed"]), 0x0D5])
                 if "order_seed" in mix else rng)
    order = order_rng.permutation(n)
    reqs = []
    for rid, k in enumerate(order):
        p = int(prompts[k])
        toks = rng.integers(0, vocab, size=(p,), dtype=np.int32)
        reqs.append(request_cls(rid=rid, arrival=0.0, prompt_len=p,
                                gen_len=int(gens[k]), slo_cycles=None,
                                tokens=toks))
    return reqs


def stamped_types(on_stamp=None):
    """A ``Request`` subclass that stamps the host clock, and a frozen
    ``WorkloadSpec`` subclass whose ``build`` makes the job of them.

    ``on_stamp(request, field, t)`` is called after each stamp; the harness
    uses it to open and close the profiler between two steps.
    """
    from repro.serve import Request, WorkloadSpec

    class StampedRequest(Request):
        def __setattr__(self, name, value):
            object.__setattr__(self, name, value)
            if name in STAMPED and value is not None:
                t = time.perf_counter()
                self.__dict__.setdefault("wall", {})[name] = t
                if on_stamp is not None:
                    on_stamp(self, name, t)

    @dataclasses.dataclass(frozen=True)
    class StampedSpec(WorkloadSpec):
        mix: str = "{}"            # the traffic file, as JSON text

        def build(self, *, model=None, with_tokens=True):
            return build_requests(json.loads(self.mix), self.num_requests,
                                  self.seed, self.vocab_size, StampedRequest)

    return StampedRequest, StampedSpec
