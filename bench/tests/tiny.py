"""Cells of BENCHMARK.json cut to sizes a CPU test run can hold, driven
through the harness with the chip check left out."""

from __future__ import annotations

import jax

from bench import harness, run as bench_run


def cell(name: str, chips: int | None = None) -> harness.Cell:
    cell = harness.load_cell(name)
    if chips is not None:
        cell.chips = chips
    c, mix = cell.config, cell.traffic
    kind = mix["kind"]
    if kind == "halo":
        c.update(rows_per_chip=32, cols=256, sweeps_per_check=8)
    else:
        c.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=2, intermediate_size=128,
                 vocab_size=256, padded_heads=16)
    if kind == "serve":
        c["serve"] = {"slots": 4, "page_size": 8, "max_seq": 64}
        mix.update(arrivals={"dist": "poisson", "rate_per_s": 8.0},
                   check_requests=3, trace_seconds=0.5,
                   prompt={"dist": "lognormal", "median": 8, "sigma": 1.0,
                           "min": 2, "max": 40},
                   output={"dist": "lognormal", "median": 6, "sigma": 0.8,
                           "min": 2, "max": 20})
    if kind == "train":
        # a rate at which every tiny weight moves by more than its
        # bf16 rounding
        c["optimizer"]["lr"] = 1e-2
        mix.update(seq=64, trace_seconds=0.5)
    return cell


def drive(name: str, *, seed: int = 2**31 + 99, seconds: float = 0.5,
          control: str | bool | None = None, chips: int | None = None
          ) -> dict:
    """One run of the cut cell on the CPU (on ``chips`` host devices, if
    given); returns the result line."""
    c = cell(name, chips)
    devs = jax.devices()[:c.chips]
    r = harness.Run(c, seed=seed, seconds=seconds, trace=False,
                    devices=devs)
    r.peaks = harness.peaks("TPU v5 lite")
    r.control = control
    return bench_run.execute(r)
