"""The control of each configuration at a size a test run can hold: the
reference computed at the next precision below the one the configuration states
must fail at least one of the numbers `correct` compares, and the reference at
the stated precision must pass them all.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/chip/tests/test_control.py -q
"""
import json
import os
import random

import checks
import refcheck
from reference import bert, gpt2
from runners import train

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def test_serve_control_fails_and_reference_passes():
    cfg = load("tiny_gpt.json")
    rng = random.Random(5)
    samples = [{"tokens": [rng.randrange(cfg["vocab_size"]) for _ in range(8)],
                "served": [rng.randrange(cfg["vocab_size"])
                           for _ in range(118)]} for _ in range(12)]
    # served tokens that ARE the reference's own choice: greedy decoding by
    # the reference, position by position, is what a sound program serves
    import jax.numpy as jnp
    import numpy as np
    fwd = gpt2.make_forward(cfg, "float32")
    params = gpt2.init_params(cfg, 31)
    for s in samples:
        seq = list(s["tokens"])
        for _ in range(40):
            pad = np.zeros((1, 128), np.int32)
            pad[0, :len(seq)] = seq
            seq.append(int(jnp.argmax(fwd(params, jnp.asarray(pad))
                                      [0, len(seq) - 1])))
        s["greedy"] = seq[8:]
    # the control's reading does not depend on what was served: it is the
    # gap of the token the lower precision puts first, at every position
    low = refcheck.serve_numbers(gpt2, cfg, 31, samples, ["bfloat16"])
    for s in samples:
        s["served"] = s["greedy"]
    out = refcheck.serve_numbers(gpt2, cfg, 31, samples, ["float32"])
    limits = cfg["check"]["limits"]
    sound = {k: out["float32"][k] for k in limits}
    control = {k: low["bfloat16"][k] for k in limits}
    assert checks.judge(sound, limits, "sound") is True
    assert checks.judge(control, limits, "control") is False


def test_train_control_fails_and_stated_precision_passes():
    cfg = load("tiny_bert.json")
    cfg["job"]["param_dtype"] = "bfloat16"      # the precision the job states
    hp = cfg["job"]["optimizer_params"]
    batches = train.first_batches(train.make_corpus(cfg, 9),
                                  cfg["job"]["batch_size"], 3)
    reference = bert.follow(cfg, hp, 9, batches)
    stated = checks.train_numbers(bert.follow(cfg, hp, 9, batches,
                                              "bfloat16"), reference)
    control = checks.train_numbers(bert.follow(cfg, hp, 9, batches,
                                               "float8"), reference)
    limits = cfg["check"]["limits"]
    assert checks.judge(stated, limits, "stated") is True
    assert checks.judge(control, limits, "control") is False
