"""Pipeline-parallel GPT training example: a data x pipe mesh with the
GPipe or 1F1B schedule (reference analog: none — the reference's
distributed story stops at data parallelism over kvstore; this is the
pp axis of the dp/tp/sp/ep/pp set, see docs/parallelism.md).

Run on any host — the mesh uses virtual CPU devices when no TPUs exist:

    python example/distributed/train_pipeline.py --schedule 1f1b

The 1F1B schedule keeps activation memory O(stages) regardless of the
microbatch count (GPipe's grows with it): raise --microbatches to
shrink the pipeline bubble for free.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dp", type=int, default=2, help="data-parallel")
    ap.add_argument("--stages", type=int, default=4,
                    help="pipeline stages (pipe axis)")
    ap.add_argument("--layers", type=int, default=8,
                    help="transformer cells (must divide by stages)")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--schedule", choices=["gpipe", "1f1b"],
                    default="1f1b")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--fixed-batch", action="store_true",
                    help="train on ONE fixed batch (overfit sanity "
                         "check / CI smoke)")
    ap.add_argument("--accel", action="store_true",
                    help="use the live accelerator mesh; default is a "
                         "virtual CPU mesh")
    args = ap.parse_args()

    import jax
    n_dev = args.dp * args.stages
    if not args.accel:
        # virtual CPU mesh (same path the test suite and the driver
        # dryrun use); MUST be configured before any jax.devices() call
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n_dev)
    elif len(jax.devices()) < n_dev:
        raise SystemExit(f"--accel needs {n_dev} devices, have "
                         f"{len(jax.devices())}")

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.models import bert, gpt

    mx.random.seed(0)
    net = gpt.GPTModel(vocab_size=args.vocab, max_length=args.seq_len,
                       units=64, num_layers=args.layers, num_heads=4,
                       dropout=0.0)
    net.initialize(init=mx.init.Normal(0.05))
    rng = np.random.default_rng(0)
    warm = mx.nd.array(np.zeros((1, args.seq_len), np.int32),
                       dtype="int32")
    with mx.autograd.pause():
        net(warm)                      # settle deferred shapes

    mesh = parallel.make_mesh({"data": args.dp, "pipe": args.stages},
                              devices=jax.devices()[:n_dev])
    trainer = parallel.SPMDTrainer(
        net, bert.MLMPretrainLoss(args.vocab), "adam",
        {"learning_rate": 3e-3}, mesh=mesh,
        pipeline_axis="pipe", pipeline_microbatches=args.microbatches,
        pipeline_schedule=args.schedule)

    print(f"mesh data={args.dp} x pipe={args.stages}, "
          f"{args.layers} cells ({args.layers // args.stages}/stage), "
          f"schedule={args.schedule}, M={args.microbatches}")
    fixed = rng.integers(0, args.vocab,
                         (args.batch_size, args.seq_len))
    for step in range(args.steps):
        ids = fixed if args.fixed_batch else rng.integers(
            0, args.vocab, (args.batch_size, args.seq_len))
        labels = np.roll(ids, -1, axis=1).astype(np.float32)
        loss = float(trainer.step(ids.astype(np.int32), labels))
        print(f"step {step:3d}  loss {loss:.4f}")
    trainer.sync_to_block()            # trained weights back to the net
    print("done: final loss", round(loss, 4))


if __name__ == "__main__":
    main()
