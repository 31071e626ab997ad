"""The plain reference against the program's own forward, at a size the
CPU holds, and the seeded weights both sides share."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths
import gen
import harness
import reference
import spec

TINY = {"hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "vocab_size": 512, "rms_norm_eps": 1e-5, "rope_theta": 1e6,
        "tie_word_embeddings": False,
        "serving": {"quantization": "ternary_packed", "ternary_min_dim": 16,
                    "dtype": "bfloat16", "param_dtype": "float32",
                    "cache_dtype": "bfloat16"}}
SEED = 2**33 + 5


@pytest.fixture(scope="module")
def program():
    from repro.models import LM
    dense = spec.load_family(_paths.BENCH, "dense")
    model = LM(dense.model_config(TINY))
    return model, harness.make_params(model, SEED, dense.leaf)


def test_stacked_leaves_equal_per_layer_draws(program):
    _, params = program
    root = gen.root_key(SEED)
    wc = params["block0"]["ffn"]["gate"]["w_packed"]
    for l in range(TINY["num_hidden_layers"]):
        words = gen.leaf(root, "block0/ffn/gate/w_packed/packed",
                         wc.packed.shape[1:], layer=l)
        assert np.array_equal(np.asarray(words), np.asarray(wc.packed[l]))
        scale = gen.leaf(root, "block0/ffn/gate/w_packed/scale",
                         wc.scale.shape[1:], layer=l, k_in=128)
        assert np.array_equal(np.asarray(scale), np.asarray(wc.scale[l]))


def test_codes_decode_like_the_program(program):
    _, params = program
    wc = params["block0"]["mixer"]["q"]["w_packed"]
    one = wc.replace(packed=wc.packed[0], scale=wc.scale[0])
    mine = reference.decode_words(one.packed, one.k)
    assert np.array_equal(np.asarray(mine), np.asarray(one.materialize()))
    frac = float(jnp.mean(mine != 0))
    assert 0.45 < frac < 0.55          # half nonzero, as stated


def test_large_seed_is_accepted():
    a = jax.random.bits(gen.root_key(2**31 + 3), (4,))
    b = jax.random.bits(gen.root_key(2**31 + 4), (4,))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_reference_matches_prefill_and_decode(program):
    """Prefill then decode through the program's cache agree with the
    reference's full causal forward, to bf16 rounding."""
    model, params = program
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, TINY["vocab_size"], (1, 12), dtype=np.int32)
    cache, logits = model.prefill(params, {"tokens": jnp.asarray(prompt)},
                                  max_len=32)
    got = [np.asarray(logits[0, -1], np.float32)]
    toks = list(prompt[0])
    for _ in range(4):
        nxt = int(np.argmax(got[-1]))
        toks.append(nxt)
        lg, cache = model.decode_step(params, cache,
                                      jnp.asarray([[nxt]], jnp.int32))
        got.append(np.asarray(lg[0, -1], np.float32))
    seq = np.asarray([toks + [0] * 4], np.int32)
    pos = np.asarray([(0, 11 + j) for j in range(5)], np.int32)
    ref = np.asarray(reference.logits_at(TINY, SEED, seq, pos))
    got = np.stack(got)
    err = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert err < 3e-2, err
    assert (got.argmax(-1) == ref.argmax(-1)).mean() >= 0.8


def test_control_is_a_lower_precision():
    """The float8 control moves the logits far more than bf16 rounding."""
    rng = np.random.default_rng(1)
    seq = rng.integers(0, TINY["vocab_size"], (2, 24), dtype=np.int32)
    pos = np.asarray([(i, j) for i in range(2) for j in range(8, 24)],
                     np.int32)
    ref = np.asarray(reference.logits_at(TINY, SEED, seq, pos))
    low = np.asarray(reference.logits_at(TINY, SEED, seq, pos, lowp=True))
    err = np.linalg.norm(low - ref) / np.linalg.norm(ref)
    assert err > 5e-2, err


def test_pack_sequences_positions():
    seqs = [(np.arange(3), [7, 8]), (np.arange(5), [9])]
    tokens, pos, tgt, owner = reference.pack_sequences(seqs, pad_to=8)
    assert tokens.shape == (2, 8)
    assert list(tokens[0, :4]) == [0, 1, 2, 7]
    assert [tuple(p) for p in pos] == [(0, 2), (0, 3), (1, 4)]
    assert list(tgt) == [7, 8, 9] and list(owner) == [0, 0, 1]
