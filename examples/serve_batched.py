"""Batched serving example — the managed serving runtime end to end.

Submits a queue of mixed-length requests to the ServeEngine (paged KV
cache + continuous batching; repro/serve) instead of hand-rolling a
prefill/decode loop, prints each request's greedy completion, and shows
the MDMP serve-schedule decision the managed runtime made for the queue.

    PYTHONPATH=src python examples/serve_batched.py [arch]
"""

import sys

import jax
import numpy as np

from repro import configs
from repro.core import managed
from repro.models.model import Model
from repro.parallel.sharding import MeshCtx, infer_shardings
from repro.serve.engine import ServeEngine


def main() -> None:
    arch = sys.argv[1] if len(sys.argv) > 1 else "mamba2-130m"
    cfg = configs.get_reduced(arch)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    ctx = MeshCtx.from_mesh(mesh, mdmp_mode="auto")
    model = Model(cfg, ctx)
    params = model.init(jax.random.key(0),
                        infer_shardings(model.param_specs(), mesh))

    engine = ServeEngine(model, mesh, params, slots=2, max_seq=64,
                         page_size=8, schedule="auto")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size - 1, size=p).astype(np.int32)
               for p in (8, 3, 12, 5)]
    rids = [engine.submit(p, 16) for p in prompts]
    out = engine.run()

    for i, rid in enumerate(rids):
        print(f"request {rid}: prompt={prompts[i].tolist()} "
              f"-> {out[rid].tolist()}")
    s = engine.metrics.summary()
    print(f"{s['useful_tok_s']:.1f} useful tok/s over {s['quanta']} quanta, "
          f"occupancy {s['occupancy']:.2f}")
    for rec in managed.decision_log():
        if rec.op == "serve_schedule":
            print(f"managed decision: serve_schedule({rec.mode}, "
                  f"C={rec.chunks})")


if __name__ == "__main__":
    main()
