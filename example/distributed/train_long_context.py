"""Long-context training example: sequence parallelism over a ``seq``
mesh axis — ring attention (default) or DeepSpeed-Ulysses — optionally
with the Pallas flash kernel per block (`MXNET_USE_FUSION=1`:
blockwise ring attention, O(T_local) attention memory in every
direction).  Reference analog: none — SURVEY §5.7 marks long-context
SP as a beyond-parity capability; see docs/parallelism.md.

Run anywhere (virtual CPU mesh by default):

    python example/distributed/train_long_context.py --seq-len 512
    MXNET_SP_IMPL=ulysses python example/distributed/train_long_context.py
    MXNET_USE_FUSION=1 python example/distributed/train_long_context.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--sp", type=int, default=4,
                    help="sequence-parallel shards (seq axis)")
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--fixed-batch", action="store_true",
                    help="train on ONE fixed batch (overfit sanity "
                         "check / CI smoke)")
    ap.add_argument("--accel", action="store_true",
                    help="use the live accelerator mesh; default is a "
                         "virtual CPU mesh")
    args = ap.parse_args()

    import jax
    n_dev = args.dp * args.sp
    if not args.accel:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n_dev)
    elif len(jax.devices()) < n_dev:
        raise SystemExit(f"--accel needs {n_dev} devices, have "
                         f"{len(jax.devices())}")

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.models import bert, gpt

    mesh = parallel.make_mesh({"data": args.dp, "seq": args.sp},
                              devices=jax.devices()[:n_dev])
    mx.random.seed(0)
    # heads divisible by sp so either MXNET_SP_IMPL works
    net = gpt.GPTModel(vocab_size=args.vocab, max_length=args.seq_len,
                       units=64, num_layers=args.layers,
                       num_heads=max(4, args.sp), dropout=0.0,
                       seq_axis="seq", mesh=mesh)
    net.initialize(init=mx.init.Normal(0.05))
    # settle deferred shapes EAGERLY on one device: the seq-parallel
    # shard_map path can't run there, so this one forward runs dense
    warm = mx.nd.array(np.zeros((2, args.seq_len), np.int32),
                       dtype="int32")
    with bert.dense_attention(net), mx.autograd.pause():
        net(warm)
    trainer = parallel.SPMDTrainer(
        net, bert.MLMPretrainLoss(args.vocab), "adam",
        {"learning_rate": 3e-3}, mesh=mesh, data_axis="data",
        extra_input_shardings=None)

    sp_impl = (os.environ.get("MXNET_SP_IMPL") or "ring").lower()
    fused = os.environ.get("MXNET_USE_FUSION") == "1"
    print(f"mesh data={args.dp} x seq={args.sp}, T={args.seq_len} "
          f"(T_local={args.seq_len // args.sp}), sp_impl={sp_impl}, "
          f"flash={'on' if fused else 'off'}")
    rng = np.random.default_rng(0)
    fixed = rng.integers(0, args.vocab,
                         (args.batch_size, args.seq_len))
    for step in range(args.steps):
        ids = fixed if args.fixed_batch else rng.integers(
            0, args.vocab, (args.batch_size, args.seq_len))
        labels = np.roll(ids, -1, axis=1).astype(np.float32)
        loss = float(trainer.step(ids.astype(np.int32), labels))
        print(f"step {step:3d}  loss {loss:.4f}")
    trainer.sync_to_block()
    print("done: final loss", round(loss, 4))


if __name__ == "__main__":
    main()
