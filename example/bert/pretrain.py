#!/usr/bin/env python
"""BERT pretraining with the compiled SPMD step (reference workload:
GluonNLP scripts/bert/run_pretraining.py — the judged north-star;
SURVEY §6).

One jitted train step over a device mesh carries the model, the MLM+NSP
objective, and the optimizer; batch data is sharded over the 'data' axis
and parameters over 'model' when --tp > 1.  Synthetic token streams stand
in for the corpus (zero-egress environment).

    python example/bert/pretrain.py --arch tiny --steps 20 --cpu-mesh 8
    python example/bert/pretrain.py --arch large --batch-size 32  # on TPU
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=["tiny", "base", "large"],
                    default="tiny")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree (mesh 'model' axis)")
    ap.add_argument("--cpu-mesh", type=int, default=0,
                    help="force an N-virtual-device CPU mesh (testing)")
    ap.add_argument("--checkpoint-prefix", default=None)
    args = ap.parse_args()

    import jax
    if args.cpu_mesh:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu_mesh)

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.models import bert as bm

    n_dev = len(jax.devices())
    tp = args.tp
    dp = n_dev // tp
    mesh = parallel.make_mesh({"data": dp, "model": tp})
    print(f"devices={n_dev} mesh=dp{dp}xtp{tp} arch={args.arch}")

    mx.random.seed(0)
    factory = {"tiny": bm.bert_tiny, "base": bm.bert_base,
               "large": bm.bert_large}[args.arch]
    vocab = 512 if args.arch == "tiny" else 30522
    net = bm.BERTForPretrain(
        factory(vocab_size=vocab, dropout=0.0,
                max_length=max(args.seq_len, 64)),
        vocab_size=vocab)
    net.initialize(init=mx.init.Normal(0.02))

    B, T = args.batch_size, args.seq_len
    with mx.autograd.pause():
        net(mx.nd.array(np.zeros((2, T)), dtype=np.int32),
            mx.nd.array(np.zeros((2, T)), dtype=np.int32))

    trainer = parallel.SPMDTrainer(
        net, bm.BERTPretrainLoss(vocab), "adam",
        {"learning_rate": args.lr}, mesh=mesh, data_axis="data",
        sharding_rules=bm.tp_rules("model") if tp > 1 else None)

    ckpt = None
    if args.checkpoint_prefix:
        from incubator_mxnet_tpu.checkpoint import AsyncCheckpointer
        ckpt = AsyncCheckpointer(args.checkpoint_prefix)

    rng = np.random.default_rng(0)
    t0 = None
    for step in range(args.steps):
        ids = rng.integers(0, vocab, (B, T)).astype(np.int32)
        types = np.zeros((B, T), np.int32)
        labels = np.concatenate(
            [rng.integers(0, vocab, (B, T)),
             rng.integers(0, 2, (B, 1))], axis=1).astype(np.float32)
        loss = trainer.step(ids, types, labels)
        if step == 1:
            jax.block_until_ready(loss)
            t0 = time.perf_counter()       # skip compile step
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step}: loss {float(loss):.4f}")
        if ckpt is not None and step and step % 50 == 0:
            trainer.sync_to_block()
            ckpt.save(step, {k: p.data()
                             for k, p in net.collect_params().items()})
    jax.block_until_ready(loss)
    if t0 is not None and args.steps > 2:
        sps = (args.steps - 2) * B / (time.perf_counter() - t0)
        print(f"throughput: {sps:.2f} samples/s "
              f"({sps / n_dev:.2f}/device)")
    if ckpt is not None:
        ckpt.wait_until_finished()
    print("done")


if __name__ == "__main__":
    main()
