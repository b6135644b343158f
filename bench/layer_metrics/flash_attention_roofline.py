"""Least time of each call of the flash attention forward kernel (the
larger of its causal operations over the bf16 peak and its q, k, v, o
bytes over HBM bandwidth) over the call's device time, summed over the
traced window (``kernels/flash_attention.py``).  The backward and the
log-sum-exp pass are plain XLA and count in ``mfu.train`` only."""

from bench import counts, trace_reduce


def read(r):
    t = r.reduced
    if t is None:
        return None
    c, job = r.cfg, r.traffic
    # the kernel's output: [batch, padded heads, seq, head size]
    out = (f"bf16[{job['batch']},{c['padded_heads']},{job['seq']},"
           f"{c['hidden_size'] // c['num_attention_heads']}]")
    calls = trace_reduce.kernel_events(t, out, device=0)
    busy = sum(e.dur for e in calls) / 1e9
    if busy <= 0:
        return None
    flops, nbytes = counts.flash_call(r.cfg, r.traffic["batch"],
                                      r.traffic["seq"])
    least = max(flops / r.peaks["bf16_flops_per_s"],
                nbytes / r.peaks["hbm_bytes_per_s"])
    return 100.0 * len(calls) * least / busy
