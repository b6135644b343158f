"""Driver of the ``train`` traffic kind: back-to-back optimizer steps of
the program's ``TrainLoop``.

Set-up builds one object, the loop with its compiled step
(``build_train_step``) and its state (the benchmark's seeded weights, the
program's AdamW state), and drives it through the job's first
``check_steps`` steps on token rows drawn from the seed, reading after the
first step the gradient the optimizer got (from its first moment) and
after the last the change of every parameter leaf.  The window then runs
the same loop on from there for ``--seconds``.

``correct``: once the window has closed and the program's state is freed,
the float32 reference takes the same steps from the same weights and
tokens.  Compared, each against its limit: every step's loss, and by the
worst leaf the gap between the program's and the reference's norms of
the first gradient and of the parameters' change, as a share of the
reference's norm of that leaf or of the median leaf, whichever is larger.
"""

from __future__ import annotations

import functools
import json
import time

import numpy as np

from bench import gen, harness
from bench.harness import BENCH, Run, span
from bench.model import model_config

#: limits of the three numbers compared, each between the largest reading
#: of sound runs over 12 seeds and the smallest of the control (fp8
#: throughout) or of a planted fault; the readings are in PERF.md
#: (section 2)
MAX_LOSS_GAP = 3e-4
MAX_GRAD_GAP = 0.1
MAX_UPDATE_GAP = 0.2
#: leaves whose reference gradient norm is under this share of the median
#: leaf's move by round-off alone and are left out of the change
NOUGHT_GRAD = 1e-3


class Data:
    """The loop's data feed: token rows drawn from the seed."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int):
        self.seed, self.batch, self.seq, self.vocab = seed, batch, seq, vocab

    def global_batch_at(self, step: int) -> dict:
        return gen.train_batch(self.seed, step, self.batch, self.seq,
                               self.vocab)


def opt_settings(c: dict) -> dict:
    return c["optimizer"]


def build(r: Run):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models.model import Model
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.parallel.sharding import MeshCtx
    from repro.train.train_loop import (TrainLoop, TrainLoopConfig,
                                        build_train_step)
    from bench.reference import weights

    c, job = r.cfg, r.traffic
    o = opt_settings(c)
    mc = model_config(c)
    mesh = jax.make_mesh((len(r.devices), 1), ("data", "model"),
                         devices=r.devices)
    model = Model(mc, MeshCtx.from_mesh(mesh))
    opt_cfg = AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                          weight_decay=o["weight_decay"],
                          clip_norm=o["clip_norm"],
                          warmup_steps=o["warmup_steps"],
                          total_steps=o["total_steps"],
                          moment_dtype=o["moment_dtype"])
    step_fn, pshard, bshard = build_train_step(model, opt_cfg, mesh)
    data = Data(r.seed, job["batch"], job["seq"], c["vocab_size"])
    loop = TrainLoop(step_fn, model, opt_cfg, data,
                     TrainLoopConfig(total_steps=0, ckpt_every=0,
                                     ckpt_dir=str(BENCH / ".ckpt"),
                                     max_retries=0),
                     pshard, bshard)
    params = weights.make(harness.jax_key(r.seed, 3), c, pshard)
    opt = adamw_init(params, opt_cfg)
    opt["step"] = jax.device_put(opt["step"], NamedSharding(mesh, P()))
    return loop, params, opt


def steps(loop, params, opt, step: int, n: int):
    """``n`` steps of the loop from ``step``."""
    loop.cfg.total_steps = step + n
    out = loop.run(params, opt, step)
    return out["params"], out["opt"], out["step"]


def leaf_norms(tree) -> dict[str, float]:
    import jax
    import jax.numpy as jnp
    from bench.reference import weights
    flat = weights.flatten(tree)
    norm = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))))
    return {k: float(norm(v)) for k, v in flat.items()}


def change_norms(params, seed_key, c) -> dict[str, float]:
    """Per leaf, the norm of (params - the seeded start), the start drawn
    again leaf by leaf."""
    import jax
    import jax.numpy as jnp
    from bench.reference import weights
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    return {k: float(diff(v, weights.make_leaf(seed_key, c, k)))
            for k, v in weights.flatten(params).items()}


def run(r: Run) -> None:
    import jax
    c, job = r.cfg, r.traffic
    n_check = int(job["check_steps"])
    b1 = opt_settings(c)["b1"]
    loop, params, opt = build(r)
    seed_key = harness.jax_key(r.seed, 3)
    with span("bench.train.checked_steps"):
        params, opt, step = steps(loop, params, opt, 0, 1)
        grad = {k: v / (1.0 - b1) for k, v in leaf_norms(opt["mu"]).items()}
        params, opt, step = steps(loop, params, opt, step, n_check - 1)
        change = change_norms(params, seed_key, c)
    losses = [h["loss"] for h in loop.history[:n_check]]
    counter = harness.CompileCounter()
    trace_from = r.seconds - float(job.get("trace_seconds", r.seconds))
    r.setup_done()
    counter.armed = True
    t0 = time.perf_counter()
    n = 0
    traced_from = None
    while time.perf_counter() - t0 < r.seconds:
        if r.trace and traced_from is None and \
                time.perf_counter() - t0 >= trace_from:
            r.start_trace()
            traced_from = n
        with span("bench.train.loop"):
            params, opt, step = steps(loop, params, opt, step, 1)
        n += 1
    t1 = time.perf_counter()
    counter.armed = False
    r.stop_trace()
    tokens = job["batch"] * job["seq"]
    r.attempted = n
    r.metric("train_tokens_per_s", n * tokens / (t1 - t0), "tokens/s")
    r.counters.update(steps=n, window_s=t1 - t0, tokens_per_step=tokens,
                      traced_steps=(n - traced_from
                                    if traced_from is not None else 0),
                      compiles_in_window=counter.count)
    r.peak_bytes = harness.peak_bytes(r.devices)
    finite = all(np.isfinite(h["loss"]) for h in loop.history)
    del params, opt, loop
    jax.clear_caches()
    if not finite:
        r.failed = 1
    verify(r, losses, grad, change)


def reference_steps(r: Run, quant: str | None = None, half: bool = False):
    """The reference's losses, first-gradient norms and change norms over
    the job's checked steps (two at most, so that float32 weights and two
    gradients fit the chip beside the activations).  ``half`` plants a
    fault: the second half of each row's positions left out of the
    loss."""
    import jax
    import jax.numpy as jnp
    from bench.reference import transformer, weights
    c, job = r.cfg, r.traffic
    o = opt_settings(c)
    cj = json.dumps(c, sort_keys=True)
    seed_key = harness.jax_key(r.seed, 3)
    n_check = int(job["check_steps"])
    if n_check not in (1, 2):
        raise ValueError("the reference takes one or two steps")

    @jax.jit
    def grad_fn(w, tokens, labels):
        return jax.value_and_grad(transformer.loss)(
            w, tokens, labels, json.loads(cj), quant)

    def batch(step):
        b = gen.train_batch(r.seed, step, job["batch"], job["seq"],
                            c["vocab_size"])
        labels = b["labels"]
        if half:
            labels = np.where(np.arange(job["seq"]) < job["seq"] // 2,
                              labels, -1)
        return jnp.asarray(b["tokens"]), jnp.asarray(labels)

    # the program's start: the seeded weights as served, widened (a
    # control stores them in its own precision)
    store = functools.partial(transformer.rounded, quant=quant)
    w = jax.tree.map(lambda a: store(a.astype(jnp.float32)),
                     weights.make(seed_key, c))
    loss1, g1 = grad_fn(w, *batch(0))
    s1 = transformer.clip_scale(transformer.global_norm(g1), o)
    g1 = jax.tree.map(lambda g: np.asarray(g) * s1, g1)       # host
    grad = {k: float(np.linalg.norm(v))
            for k, v in weights.flatten(g1).items()}
    losses = [float(loss1)]
    if n_check == 1:
        w1 = _adam(w, g1, None, o, 1, store)
        change = {k: float(jnp.linalg.norm(
            v - weights.make_leaf(seed_key, c, k).astype(jnp.float32)))
            for k, v in weights.flatten(w1).items()}
        return losses, grad, change
    w = _adam(w, g1, None, o, 1, store)                       # p1
    loss2, g2 = grad_fn(w, *batch(1))
    losses.append(float(loss2))
    s2 = transformer.clip_scale(transformer.global_norm(g2), o)
    g2 = jax.tree.map(lambda g: g * s2, g2)
    w = _adam(w, g2, g1, o, 2, store)                         # p2
    change = {k: float(jnp.linalg.norm(
        v - weights.make_leaf(seed_key, c, k).astype(jnp.float32)))
        for k, v in weights.flatten(w).items()}
    return losses, grad, change


def _adam(w, g, g_prev, o: dict, step: int, store):
    """AdamW step ``step`` (1 or 2) on float32 leaves from clipped
    gradients: moments rebuilt from the previous gradient, bias-corrected,
    decoupled weight decay, the result kept as ``store`` rounds it; each
    leaf of ``w`` is donated as it goes."""
    import jax
    import jax.numpy as jnp
    from bench.reference import transformer
    b1, b2, eps, wd = o["b1"], o["b2"], o["eps"], o["weight_decay"]
    lr = transformer.lr_at(step, o)

    @functools.partial(jax.jit, donate_argnums=0)
    def one(p, g, gp):
        m, v = (1 - b1) * g, (1 - b2) * g * g
        if gp is not None:
            m, v = m + b1 * (1 - b1) * gp, v + b2 * (1 - b2) * gp * gp
        mh, vh = m / (1 - b1 ** step), v / (1 - b2 ** step)
        return store(p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p))

    leaves, treedef = jax.tree.flatten(w)
    gs = jax.tree.leaves(g)
    gps = jax.tree.leaves(g_prev) if g_prev is not None else [None] * len(gs)
    new = [one(p, jnp.asarray(gl), None if gp is None else jnp.asarray(gp))
           for p, gl, gp in zip(leaves, gs, gps)]
    return jax.tree.unflatten(treedef, new)


def gaps(prog: dict[str, float], ref: dict[str, float],
         keep: set[str] | None = None) -> dict[str, float]:
    """Per leaf, |program norm - reference norm| over the larger of that
    leaf's reference norm and the median leaf's."""
    names = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in names]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in names}


def verify(r: Run, losses, grad, change) -> None:
    quant = r.control
    if quant == "half_batch":
        # a planted fault: the reference with half of each row left out,
        # in the program's place
        losses, grad, change = reference_steps(r, None, half=True)
    elif quant:
        # the control: the reference in lower precision in the program's
        # place
        losses, grad, change = reference_steps(r, quant)
    ref_losses, ref_grad, ref_change = reference_steps(r)
    med = float(np.median(list(ref_grad.values())))
    moved = {k for k, v in ref_grad.items() if v >= NOUGHT_GRAD * med}
    grad_gaps = gaps(grad, ref_grad)
    change_gaps = gaps(change, ref_change, moved)
    r.counters.update(losses=losses, ref_losses=ref_losses,
                      left_out=sorted(set(ref_grad) - moved),
                      grad_gaps=grad_gaps, change_gaps=change_gaps)
    harness.log(f"train: losses {losses} vs {ref_losses}; grad gaps "
                f"{grad_gaps}; change gaps {change_gaps}")
    r.check("train_loss_gap", max(abs(a - b) / abs(b) for a, b in
                                  zip(losses, ref_losses)), MAX_LOSS_GAP)
    r.check("train_grad_norm_gap", max(grad_gaps.values()), MAX_GRAD_GAP)
    r.check("train_change_norm_gap", max(change_gaps.values()),
            MAX_UPDATE_GAP)
