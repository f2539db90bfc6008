#!/usr/bin/env python
"""Mixture-of-Experts classifier with expert parallelism (beyond-parity
capability; see docs/parallelism.md).  Trains a small MoE network under
a data x expert mesh — expert parameters genuinely sharded, GSPMD
placing the dispatch collectives — with the Switch load-balancing aux
loss in the objective.

    python example/moe/train_moe.py --cpu --steps 20
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", type=int, default=4)
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--aux-weight", type=float, default=0.01)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 8)

    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel
    from incubator_mxnet_tpu.models import moe

    n_dev = len(jax.devices())
    e_ax = min(args.experts, max(1, n_dev // 2))
    while args.experts % e_ax or n_dev % e_ax:
        e_ax -= 1          # the stacked expert dim must shard evenly
    mesh = parallel.make_mesh({"data": n_dev // e_ax, "expert": e_ax})
    print(f"mesh: data={n_dev // e_ax} x expert={e_ax}")

    class Net(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.inp = gluon.nn.Dense(32, flatten=False, in_units=8)
                self.moe = moe.MoEFFN(32, 64, args.experts,
                                      top_k=args.top_k,
                                      capacity_factor=2.0)
                self.head = gluon.nn.Dense(4, flatten=False, in_units=32)

        def hybrid_forward(self, F, x):
            out, aux = self.moe(self.inp(x))
            return self.head(out).reshape((-1, 4)), aux

    class Loss(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, scores, aux, labels):
            return self.ce(scores, labels).mean() + args.aux_weight * aux

    mx.random.seed(0)
    rng = np.random.default_rng(0)
    W_true = rng.standard_normal((8, 4)).astype(np.float32)
    X = rng.standard_normal((16, 4, 8)).astype(np.float32)
    Y = (X.reshape(-1, 8) @ W_true).argmax(-1).astype(np.float32)

    net = Net()
    net.initialize(init=mx.init.Xavier())
    with mx.autograd.pause():
        net(mx.nd.array(X))
    tr = parallel.SPMDTrainer(net, Loss(), "adam",
                              {"learning_rate": 5e-3}, mesh=mesh,
                              data_axis="data",
                              sharding_rules=moe.ep_rules("expert"),
                              shard_optimizer_state=True, donate=False)
    for step in range(1, args.steps + 1):
        loss = float(tr.step(X, Y))
        if step % 5 == 0 or step == 1:
            print(f"step {step:3d}  loss {loss:.4f}")

    w1 = next(v for p, v in zip(tr._trainable, tr._tr_vals)
              if p.name.endswith("_w1"))
    per_dev = w1.addressable_shards[0].data.shape[0]
    print(f"expert shards: {w1.shape[0]} experts, {per_dev}/device")
    print(f"final loss {loss:.4f}")


if __name__ == "__main__":
    main()
