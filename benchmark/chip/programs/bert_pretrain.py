"""The pretraining job as `bench.py:_bench_bert` and `chip_smoke.py` build it:
``models.bert.BERTForPretrain`` + ``BERTPretrainLoss``, ``net.cast`` to the
job's type, ``parallel.SPMDTrainer`` on ``make_mesh(job["mesh"])``.

The weights are the reference's (made from the seed by
``reference/<name>.init_params``) moved into the program's layout.
"""
import jax
import numpy as np


def model_kwargs(cfg):
    """The source's config.json keys -> ``BERTModel`` arguments.  The model
    has one dropout rate for embeddings, attention output and FFN."""
    if cfg["attention_probs_dropout_prob"] != cfg["hidden_dropout_prob"]:
        raise ValueError("BERTModel takes one dropout rate; the configuration "
                         "states two that differ")
    return dict(vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
                hidden_size=cfg["intermediate_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                max_length=cfg["max_position_embeddings"],
                token_type_vocab=cfg["type_vocab_size"],
                dropout=cfg["hidden_dropout_prob"])


def build_net(cfg):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models import bert
    net = bert.BERTForPretrain(bert.BERTModel(**model_kwargs(cfg)),
                               vocab_size=cfg["vocab_size"])
    net.initialize(mx.init.Zero())
    net.cast(cfg["job"]["param_dtype"])
    T = cfg["job"]["seq_len"]
    zeros = mx.nd.array(np.zeros((2, T)), dtype=np.int32)
    with mx.autograd.pause():
        net(zeros, zeros)                # settle deferred shapes
    return net


def leaves(net):
    """The program's parameters beside the reference's names:
    ``[(reference leaf, Parameter, is_matrix)]``.  A ``Dense`` weight is
    ``(out, in)`` where the reference's is ``(in, out)``."""
    b = net.bert
    out = [("word_emb", b.word_embed.weight, False),
           ("type_emb", b.token_type_embed.weight, False),
           ("pos_emb", b.position_weight, False),
           ("emb_ln_g", b.embed_ln.gamma, False),
           ("emb_ln_b", b.embed_ln.beta, False)]
    for i, cell in enumerate(b.encoder._children.values()):
        at, ffn = cell.attention, cell.ffn
        for ref, dense in (("q", at.query), ("k", at.key), ("v", at.value),
                           ("o", at.proj)):
            out += [(f"w{ref}.{i}", dense.weight, True),
                    (f"b{ref}.{i}", dense.bias, False)]
        out += [(f"wfc.{i}", ffn.ffn_1.weight, True),
                (f"bfc.{i}", ffn.ffn_1.bias, False),
                (f"wproj.{i}", ffn.ffn_2.weight, True),
                (f"bproj.{i}", ffn.ffn_2.bias, False)]
        for ln in ("ln1", "ln2"):
            out += [(f"{ln}_g.{i}", getattr(cell, ln).gamma, False),
                    (f"{ln}_b.{i}", getattr(cell, ln).beta, False)]
    for ref, dense in (("pool", b.pooler), ("mlm", net.mlm_dense),
                       ("dec", net.mlm_decoder), ("nsp", net.nsp_classifier)):
        out += [(f"{ref}_w", dense.weight, True),
                (f"{ref}_b", dense.bias, False)]
    out += [("mlm_ln_g", net.mlm_ln.gamma, False),
            ("mlm_ln_b", net.mlm_ln.beta, False)]
    return out


def load_weights(net, ref_params):
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    table = leaves(net)

    @jax.jit
    def to_program_layout(ref):
        vals = []
        for name, _, is_matrix in table:
            base, _, layer = name.partition(".")
            a = ref[base][int(layer)] if layer else ref[base]
            vals.append(a.T if is_matrix else a)
        return vals

    for (name, param, _), value in zip(table, to_program_layout(ref_params)):
        if tuple(param.shape) != tuple(value.shape):
            raise ValueError(f"{name}: program has {param.shape}, "
                             f"reference gives {value.shape}")
        param.set_data(NDArray(value))


def build_trainer(cfg, net):
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.models import bert
    job = cfg["job"]
    n = int(np.prod(list(job["mesh"].values())))
    mesh = parallel.make_mesh(dict(job["mesh"]), devices=jax.devices()[:n])
    return parallel.SPMDTrainer(
        net, bert.BERTPretrainLoss(cfg["vocab_size"]), job["optimizer"],
        dict(job["optimizer_params"]), mesh=mesh, data_axis="data")


def make_loader(corpus, batch_size):
    """``gluon.data.DataLoader`` over an ``ArrayDataset``, in process."""
    from incubator_mxnet_tpu import gluon
    ds = gluon.data.ArrayDataset(*corpus)
    return gluon.data.DataLoader(ds, batch_size=batch_size, shuffle=False,
                                 last_batch="discard", num_workers=0)


def leaf_values(net, trainer, what):
    """``{reference leaf: array}`` of the trainer's live parameters
    (``what == "params"``), of the net's untouched starting values
    (``"start"``) or of Adam's first moment (``"m"``)."""
    table = leaves(net)
    if what == "start":
        return {name: p.data()._data for name, p, _ in table}
    by_name = trainer.params
    if what == "m":
        by_name = dict(zip(by_name, trainer._opt_state["m"]))
    return {name: by_name[p.name] for name, p, _ in table}
