"""`opcount.py` against numbers worked by hand for both configurations."""
import json
import os

import opcount

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(CHIP, "configs", name + ".json")) as f:
        return json.load(f)


def test_gpt2_medium_counts():
    c = cfg("gpt2-medium-serve")
    # a layer: 4 x 1024^2 + 2 x 1024 x 4096 = 12,582,912; x 24 = 301,989,888;
    # head 50257 x 1024 = 51,463,168
    assert opcount.gpt_matmul_params(c) == 301_989_888 + 51_463_168
    # every parameter: the published 354,823,168 (gpt2-medium)
    assert opcount.gpt_param_count(c) == 354_823_168
    # KV: 2 x 24 x 1024 x 4 B = 196,608 B a token
    assert opcount.gpt_kv_bytes_per_token(c, 4) == 196_608
    flops, nbytes = opcount.gpt_decode_step(c, 48, 12_000, 4, 4)
    # 2 x 353,453,056 x 48 + 4 x 24 x 1024 x 12,000
    assert flops == 2 * 353_453_056 * 48 + 4 * 24 * 1024 * 12_000
    assert nbytes == 354_823_168 * 4 + 196_608 * (12_000 + 48)


def test_bert_large_counts():
    c = cfg("bert-large-pretrain")
    # encoder 301,989,888 + MLM transform 1,048,576 + decoder 1024 x 30522
    assert opcount.bert_matmul_params(c) == 301_989_888 + 1_048_576 \
        + 31_254_528
    flops = opcount.bert_train_step(c, 64, 128)
    # 6 x 334,292,992 x 8192 + 12 x 24 x 128^2 x 1024 x 64
    assert flops == 6.0 * 334_292_992 * 8192 + 12.0 * 24 * 16384 * 1024 * 64
    assert 16.7e12 < flops < 16.8e12


def test_least_seconds_names_the_bound():
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    t, bound = opcount.least_seconds(197e12, 819e9 / 2, peaks)
    assert t == 1.0 and bound == "compute"
    t, bound = opcount.least_seconds(1e9, 819e9 * 2, peaks)
    assert t == 2.0 and bound == "bandwidth"
