"""Model operations of the window's steps (6 N per token plus three times
the causal attention, no recomputation) over the chip's bf16 peak times
the window (``train/train_loop.py`` steps)."""

from bench import counts


def read(r):
    n, secs = r.counters.get("steps"), r.counters.get("window_s")
    if not n or not secs:
        return None
    job = r.traffic
    flops = n * counts.train_step_flops(r.cfg, job["batch"], job["seq"])
    return 100.0 * flops / (r.peaks["bf16_flops_per_s"] * secs)
