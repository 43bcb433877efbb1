"""The plain reference agrees with the program where both compute in
float32 or better: it decodes the program's entropy streams back to what
was coded, and its autoencoders match the program's on the CPU."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from bench import checks, reference
from repro.core import bae as bae_mod
from repro.core import entropy
from repro.core import hbae as hbae_mod


def test_huffman_and_index_sets_decode_the_program_streams():
    rng = np.random.default_rng(3)
    values = np.round(rng.standard_normal(5000) * 7).astype(np.int64)
    stream = entropy.huffman_compress(values)
    got = reference.huffman_decode(stream.payload, stream.book.symbols,
                                   stream.book.lengths, stream.count)
    np.testing.assert_array_equal(got, values)
    sets = [np.sort(rng.choice(64, size=m, replace=False)).astype(np.int32)
            for m in rng.integers(0, 9, size=300)]
    dim, back = reference.index_sets(entropy.encode_index_sets(sets, 64))
    assert dim == 64 and len(back) == len(sets)
    assert all(np.array_equal(a, b) for a, b in zip(sets, back))


@pytest.fixture(scope="module")
def model():
    key = jax.random.PRNGKey(0)
    hbae = hbae_mod.hbae_init(key, in_dim=96, k=4, emb=32, hidden=64,
                              latent=16)
    bae = bae_mod.bae_init(key, in_dim=96, hidden=64, latent=8)
    comp = type("C", (), {"hbae_params": hbae, "bae_params": [bae],
                          "basis": np.eye(4)})
    return hbae, bae, checks.model_arrays(comp)


def test_autoencoders_match_the_program(model):
    hbae, bae, (ref_hbae, ref_baes, _) = model
    x = np.random.default_rng(1).standard_normal((8, 4, 96)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        y, lat = hbae_mod.hbae_apply(hbae, x)
        r_hat, lb = bae_mod.bae_apply(bae, x.reshape(32, 96))
    np.testing.assert_allclose(reference.hbae_encode(ref_hbae, x, 1), lat,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        reference.hbae_decode(ref_hbae, np.asarray(lat), 4, 1), y,
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        reference.bae_encode(ref_baes[0], x.reshape(32, 96)), lb,
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(reference.bae_decode(ref_baes[0], np.asarray(lb)),
                               r_hat, rtol=1e-4, atol=1e-4)
    # the control's precision is visibly worse
    low = reference.hbae_encode(ref_hbae, x, 1, "fp8")
    assert np.abs(low - np.asarray(lat)).max() > 1e-2


def test_gae_correction_matches_the_program_decoder():
    rng = np.random.default_rng(2)
    basis = np.linalg.qr(rng.standard_normal((16, 16)))[0].astype(np.float32)
    x = rng.standard_normal((10, 16)).astype(np.float32)
    x_r = x + 0.3 * rng.standard_normal((10, 16)).astype(np.float32)
    out, codes = __import__("repro.core.gae", fromlist=["x"]).gae_encode_blocks(
        x, x_r, basis, 0.2, 0.01)
    flat = {"index_sets": [c.indices for c in codes],
            "coeffs": np.concatenate([c.qcoeffs for c in codes]),
            "bin_exps": np.array([c.bin_exp for c in codes])}
    got = reference.gae_correct(x_r, flat, basis, 0.01)
    np.testing.assert_allclose(got, out, rtol=1e-5, atol=1e-5)
    assert (np.linalg.norm(got - x, axis=1) <= 0.2 * (1 + 1e-5)).all()
    assert dataclasses.is_dataclass(checks.Item)
