"""Tests of the family seam (``families/``) on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/test_families.py

The dense family must draw exactly the weights, and count exactly the
operations and bytes, that the harness drew and counted before the
shapes moved into ``families/dense.py``: the digests and values below
were taken with that harness.  A tied head has no ``unembed`` leaf and
still matches the program's own layout; the family is found by the
configuration's ``family`` key, and an unknown one fails loudly.
"""

from __future__ import annotations

import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import families  # noqa: E402
import flops  # noqa: E402
import run as R  # noqa: E402
import weights as W  # noqa: E402

SEED = 2**33 + 7
GELU_MQA = dict(family="dense", tie_embeddings=False, n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=1, head_dim=16, d_ff=128, vocab=256,
                qk_norm=False, mlp="gelu", rope_theta=1e4, eps=1e-6)
SWIGLU_GQA = dict(GELU_MQA, n_kv_heads=2, d_ff=96, qk_norm=True,
                  mlp="swiglu", rope_theta=1e6)

# sha256 of every leaf (path, shape, bytes) in path order
DIGESTS = {
    ("gelu_mqa", "bfloat16"):
        "a404f98229bbf51f548239d4fbeb584cf4025d765a740bca1d5505191d172014",
    ("gelu_mqa", "float32"):
        "e8874345389b103fecc5d028737fe5759c9f689356cc992a57883d410edfd5aa",
    ("swiglu_gqa", "bfloat16"):
        "c694940a66440e5c92d5da2d1b857cd82403ef99e536c8fdc162bf9568a811a8",
    ("swiglu_gqa", "float32"):
        "477607d13a91b66683e621458975b22bb9fcf0d0c26ca96a3278d463e2ac45af",
}


def digest(tree) -> str:
    import jax
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, a in sorted(leaves, key=lambda t: jax.tree_util.keystr(t[0])):
        a = np.asarray(a)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name,dtype", sorted(DIGESTS))
def test_dense_weights_are_bit_identical(name, dtype):
    m = {"gelu_mqa": GELU_MQA, "swiglu_gqa": SWIGLU_GQA}[name]
    assert digest(W.make_params(m, SEED, dtype)) == DIGESTS[(name, dtype)]


def test_reference_layers_are_the_served_layers():
    """Each layer that the reference regenerates alone, by its index
    across segments, is the served tree's slice, rounded alike."""
    params = W.make_params(SWIGLU_GQA, SEED, "bfloat16")
    for layer in range(SWIGLU_GQA["n_layers"]):
        ref = W.reference_layer(SWIGLU_GQA, SEED, layer, "bfloat16")
        served = params["segments"][0]
        for k1, k2 in (("attn", "wq"), ("attn", "k_norm"), ("mlp", "w_gate")):
            assert np.array_equal(
                np.asarray(served[k1][k2][layer], np.float32),
                np.asarray(ref[k1][k2]))
    assert np.array_equal(
        np.asarray(params["embed"], np.float32),
        np.asarray(W.reference_outer(SWIGLU_GQA, SEED, "embed", "bfloat16")))
    with pytest.raises(IndexError):
        W.reference_layer(SWIGLU_GQA, SEED, SWIGLU_GQA["n_layers"], "bfloat16")


def test_layers_are_indexed_across_segments(monkeypatch):
    """A family of two segments (a leading layer of one kind, then two
    of the dense kind): one stacked tree per segment, each layer drawn
    from its index across segments, and the reference's layer ``i`` is
    the served layer ``i`` wherever it lies."""
    import types
    from families import dense
    lead = [(("ln1",), (8,), 0), (("w",), (8, 8), 8)]
    fam = types.SimpleNamespace(
        segments=lambda m: [(1, lead), (2, dense.layer_leaves(m))],
        outer_leaves=dense.outer_leaves)
    monkeypatch.setattr(families, "load", lambda m: fam)
    m = dict(SWIGLU_GQA, n_layers=3)
    params = W.make_params(m, SEED, "float32")
    first, rest = params["segments"]
    assert first["w"].shape == (1, 8, 8) and rest["ln1"].shape == (2, 64)
    for layer, (seg, i) in enumerate([(first, 0), (rest, 0), (rest, 1)]):
        ref = W.reference_layer(m, SEED, layer, "float32")
        assert np.array_equal(np.asarray(seg["ln1"][i]), np.asarray(ref["ln1"]))
    # the second segment's first layer is layer 1, as in a dense tree
    monkeypatch.undo()
    dense_tree = W.make_params(SWIGLU_GQA, SEED, "float32")
    assert np.array_equal(np.asarray(rest["ln1"][0]),
                          np.asarray(dense_tree["segments"][0]["ln1"][1]))


class _Ctx:
    """What ``flops.mfu`` and the roofline reader read, on fixed steps."""

    def __init__(self, model, steps):
        self.model, self.family = model, families.load(model)
        self.cell = {"chips": 1}
        self.peaks = {"flops_bf16": 197e12}
        self.window_s = 2.0
        self._steps = steps

    def steps_in(self, kind=None):
        return [s for s in self._steps if kind is None or s[0] == kind]


GRANITE = json.loads((HERE / "configs" / "granite-34b.json").read_text())
STEPS = [("prefill", 0, 1, [32, 100, 7], [0, 68, 0]),
         ("decode", 1, 2, [200, 513, 1], [199, 512, 0]),
         ("prefill", 2, 3, [1536], [1504])]


def test_granite_counts_are_unchanged():
    ctx = _Ctx(GRANITE["model"], STEPS)
    assert flops.mfu(ctx, ("prefill", "decode")) == 0.2290416458071066
    assert flops.mfu(ctx, ("decode",)) == 0.006858612694416243
    assert ctx.family.paged_decode_cost(GRANITE["model"],
                                        [200, 513, 1, 16, 17], 16) == (
        201940992.0, 5857280.0)


def test_tied_head_matches_the_programs_layout():
    import jax
    from repro.configs import get_config
    from repro.models.registry import build_model
    cfg = get_config("qwen3-4b", smoke=True, tie_embeddings=True)
    m = dict(SWIGLU_GQA, tie_embeddings=True, n_layers=cfg.n_layers,
             d_model=cfg.d_model, n_heads=cfg.n_heads,
             n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, d_ff=cfg.d_ff,
             vocab=cfg.vocab)
    params = W.make_params(m, SEED, "float32")
    assert "unembed" not in params
    W.check_layout(params, jax.eval_shape(build_model(cfg).init,
                                          jax.random.PRNGKey(0)))
    untied = W.make_params(dict(m, tie_embeddings=False), SEED, "float32")
    with pytest.raises(SystemExit):
        W.check_layout(untied, jax.eval_shape(build_model(cfg).init,
                                              jax.random.PRNGKey(0)))
    # the tied tree is the untied one without its head, drawn alike
    assert digest(params) == digest(
        {k: v for k, v in untied.items() if k != "unembed"})


def test_family_is_found_by_name():
    for cfg in sorted((HERE / "configs").glob("*.json")):
        m = json.loads(cfg.read_text())["model"]
        assert families.load(m).__name__ == f"families.{m['family']}"
    with pytest.raises(LookupError, match="no model family 'moe_nowhere'"):
        families.load(dict(GELU_MQA, family="moe_nowhere"))
    with pytest.raises(LookupError, match="names no family"):
        families.load({k: v for k, v in GELU_MQA.items() if k != "family"})
    with pytest.raises(LookupError):
        W.make_params(dict(GELU_MQA, family="moe_nowhere"), SEED, "float32")


def test_unknown_family_fails_the_run(monkeypatch):
    """A configuration that names no family on disk stops the run before
    anything is built, with the configuration's name in the message."""
    cfg = dict(GRANITE, model=dict(GRANITE["model"], family="moe_nowhere"))
    real = R.load_json

    def patched(path):
        if path.name == "granite-34b.json":
            return cfg
        return real(path)

    monkeypatch.setattr(R, "load_json", patched)
    with pytest.raises(R.Failed, match="configs/granite-34b.json: no model "
                                       "family 'moe_nowhere'"):
        R.load_cell("granite-34b.codegen")
