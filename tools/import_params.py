#!/usr/bin/env python
"""Import a reference checkpoint (.params, ``arg:``/``aux:``-prefixed —
reference: python/mxnet/model.py save_checkpoint) into this framework:
strip the prefixes, optionally rename keys, and either write a gluon-style
parameter file or validate directly against a model-zoo network.

    # convert a module checkpoint into a gluon parameter file
    python tools/import_params.py ref-0007.params out.params

    # rename keys on the way through (old=new, regex via --map-re)
    python tools/import_params.py ref.params out.params \
        --map fc_weight=dense0.weight --map fc_bias=dense0.bias

    # validate shapes/names against a zoo net and save in its layout
    python tools/import_params.py ref.params out.params \
        --zoo resnet50_v1 --classes 1000

The zoo path is the insurance VERDICT r03 item 5 asked for: the day
pretrained reference artifacts are reachable, this script is the bridge
from their checkpoints to ``gluon.model_zoo`` nets (whose weights cannot
be downloaded in this zero-egress environment).
"""
import argparse
import re
import sys


def convert(loaded, maps=(), maps_re=()):
    """Strip arg:/aux: prefixes and apply renames; returns a plain dict.
    ``maps``: (old, new) exact renames.  ``maps_re``: (pattern, repl)
    regex renames applied after the exact ones."""
    out = {}
    exact = dict(maps)
    for k, v in loaded.items():
        name = k.split(":", 1)[-1] if k.startswith(("arg:", "aux:")) else k
        name = exact.get(name, name)
        for pat, repl in maps_re:
            name = re.sub(pat, repl, name)
        if name in out:
            raise SystemExit(f"rename collision: two keys map to {name!r}")
        out[name] = v
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("src", help="reference .params checkpoint")
    ap.add_argument("dst", help="output gluon-style .params file")
    ap.add_argument("--map", action="append", default=[],
                    metavar="OLD=NEW", help="exact key rename")
    ap.add_argument("--map-re", action="append", default=[],
                    metavar="PAT=REPL", help="regex key rename")
    ap.add_argument("--zoo", default=None,
                    help="validate against gluon.model_zoo.vision.<name> "
                         "and save in its parameter layout")
    ap.add_argument("--classes", type=int, default=1000)
    ap.add_argument("--allow-missing", action="store_true",
                    help="zoo: tolerate params absent from the checkpoint")
    ap.add_argument("--device", choices=["cpu", "default"], default="cpu",
                    help="repacking tensors needs no accelerator, so the "
                         "tool pins CPU by default (and leaves the chip "
                         "to whichever process is using it); 'default' "
                         "keeps the platform jax would pick")
    args = ap.parse_args()

    if args.device == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")
    import incubator_mxnet_tpu as mx

    def parse_pairs(pairs, what):
        out = []
        for p in pairs:
            if "=" not in p:
                raise SystemExit(f"--{what} wants OLD=NEW, got {p!r}")
            out.append(tuple(p.split("=", 1)))
        return out

    loaded = mx.nd.load(args.src)
    if not isinstance(loaded, dict):
        raise SystemExit(f"{args.src} holds a bare list, not a named "
                         "parameter dict — nothing to import")
    converted = convert(loaded, parse_pairs(args.map, "map"),
                        parse_pairs(args.map_re, "map-re"))

    if args.zoo:
        from incubator_mxnet_tpu.gluon.model_zoo import vision
        try:
            factory = getattr(vision, args.zoo)
        except AttributeError:
            raise SystemExit(
                f"unknown zoo model {args.zoo!r}; see "
                "gluon.model_zoo.vision for the factory names")
        net = factory(classes=args.classes)
        mx.nd.save(args.dst, converted)
        net.load_parameters(args.dst,
                            allow_missing=args.allow_missing,
                            ignore_extra=False)
        net.save_parameters(args.dst)   # re-save in the net's own layout
        print(f"[import] {len(converted)} tensors validated against "
              f"{args.zoo} and saved to {args.dst}")
    else:
        mx.nd.save(args.dst, converted)
        print(f"[import] {len(converted)} tensors written to {args.dst}")


if __name__ == "__main__":
    main()
