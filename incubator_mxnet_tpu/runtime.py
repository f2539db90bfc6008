"""``mx.runtime`` — runtime feature detection (reference:
python/mxnet/runtime.py; src/libinfo.cc ``MXLibInfoFeatures``).

The reference's feature matrix reports compile-time flags (CUDA? MKLDNN?
...).  This build's equivalents are runtime facts about the jax install
and attached devices.
"""
from __future__ import annotations

__all__ = ["Feature", "Features", "feature_list"]


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _detect():
    import jax
    try:
        platforms = {d.platform for d in jax.devices()}
    except Exception:
        platforms = set()
    has_pallas = True
    try:
        import jax.experimental.pallas  # noqa: F401
    except Exception:
        has_pallas = False
    feats = {
        # accelerator surface
        "TPU": "tpu" in platforms,
        "CUDA": False,          # by design: no CUDA in this build
        "CUDNN": False,
        "MKLDNN": False,
        "XLA": True,
        "PALLAS": has_pallas,
        "BF16": True,
        "F16C": True,
        # framework capabilities (reference flag names)
        "DIST_KVSTORE": True,   # XLA collectives over ICI/DCN
        "INT64_TENSOR_SIZE": True,
        "SIGNAL_HANDLER": True,
        "PROFILER": True,
        "OPENCV": False,
        "TENSORRT": False,
        "TVM_OP": False,
        "SSE": True,
        "DEBUG": False,
    }
    return feats


class Features(dict):
    """reference: mx.runtime.Features — dict of Feature with
    ``is_enabled``."""

    def __init__(self):
        super().__init__({k: Feature(k, v) for k, v in _detect().items()})

    def is_enabled(self, name):
        name = name.upper()
        return name in self and self[name].enabled

    def __repr__(self):
        return "[" + ", ".join(repr(v) for v in self.values()) + "]"


def feature_list():
    return list(Features().values())
