"""The reference forward against the program's own forward at a small size
on the CPU, and the weights it draws against the tree the program gets."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tinytree import BENCH, PROGRAM_STARCODER2, TINY
from harness import model as M
from harness import reference
from harness import weights as W


def _config(path, **over):
    c = json.loads(path.read_text())
    c.update(TINY, repro_config=None, **over)
    return c


CONFIGS = {"qwen3-like": _config(BENCH / "configs" / "qwen3-0.6b.json"),
           "starcoder2-like": _config(PROGRAM_STARCODER2,
                                      num_hidden_layers=3)}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_matches_program_forward(name):
    """In float32 the program's full-sequence forward and the reference
    agree to rounding at every position."""
    from repro.models import transformer as T
    c = dict(CONFIGS[name], torch_dtype="float32")
    cfg = M.program_config(c)
    params = W.make(c, seed=3)
    W.check_layout(params, M.program_params(cfg)[0])
    rng = np.random.default_rng(0)
    seqs = [rng.integers(1, c["vocab_size"], n).astype(np.int32)
            for n in (9, 23)]
    reads = [np.arange(len(s)) for s in seqs]
    ref = reference.logits_at(c, 3, seqs, reads, pad_to=16)
    for s, r in zip(seqs, ref):
        with jax.default_matmul_precision("highest"):
            got, _, _ = T.forward(cfg, params, jnp.asarray(s)[None])
        np.testing.assert_allclose(np.asarray(got[0]), r, atol=2e-4,
                                   rtol=2e-4)


def test_int8_control_departs_from_float32():
    c = CONFIGS["qwen3-like"]
    seq = [np.arange(1, 30, dtype=np.int32)]
    reads = [np.arange(29)]
    f32 = reference.logits_at(c, 5, seq, reads)[0]
    low = reference.logits_at(c, 5, seq, reads, precision="int8")[0]
    err = np.abs(low - f32).max()
    assert 1e-3 < err < 0.5 * np.abs(f32).max()


def test_layer_draw_equals_stacked_tree():
    c = CONFIGS["starcoder2-like"]
    key = W.run_key(2 ** 33 + 5)
    tree = jax.jit(lambda k: W.model(c, k))(key)
    for i in range(c["num_hidden_layers"]):
        one = W.layer(c, key, i)
        got = jax.tree.map(lambda x: x[i], tree["stack"]["p0"])
        assert jax.tree.all(jax.tree.map(
            lambda a, b: bool(jnp.array_equal(a, b)), one, got))


def test_seeds_past_32_bits_differ():
    a, b = W.run_key(7), W.run_key(7 + 2 ** 32)
    assert not jnp.array_equal(jax.random.key_data(a), jax.random.key_data(b))


def test_layout_mismatch_is_refused():
    c = CONFIGS["qwen3-like"]
    cfg = M.program_config(dict(c, intermediate_size=96))
    with pytest.raises(ValueError):
        W.check_layout(W.make(c, 1), M.program_params(cfg)[0])
