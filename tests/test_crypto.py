"""Template protection + encrypted gallery behaviour."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.crypto import (KeyedRotation, SecureGallery, cosine_scores,
                          decrypt_array, decrypt_bytes, encrypt_array,
                          encrypt_bytes)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # tier-1 must run without hypothesis installed
    HAVE_HYPOTHESIS = False


def test_rotation_preserves_cosine_exactly():
    rot = KeyedRotation(128, seed=3)
    a = jax.random.normal(jax.random.PRNGKey(0), (17, 128))
    b = jax.random.normal(jax.random.PRNGKey(1), (50, 128))
    raw = cosine_scores(a, b)
    prot = cosine_scores(rot.protect(a), rot.protect(b))
    np.testing.assert_allclose(np.asarray(raw), np.asarray(prot),
                               atol=2e-5)


def test_rotation_pins_full_precision():
    """The rotation must not run at the backend's default matmul
    precision (one bf16 pass on TPU): both directions pin HIGHEST, and
    protected cosines match raw ones to 1e-5."""
    rot = KeyedRotation(128, seed=5)
    t = jax.random.normal(jax.random.PRNGKey(4), (32, 128))
    for f in (rot.protect, rot.unprotect):
        dots = [e for e in jax.make_jaxpr(f)(t).jaxpr.eqns
                if e.primitive.name == "dot_general"]
        assert dots and all(
            e.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
            for e in dots), dots
    raw = np.asarray(cosine_scores(t, t))
    prot = np.asarray(cosine_scores(rot.protect(t), rot.protect(t)))
    np.testing.assert_allclose(prot, raw, atol=1e-5)


def test_rotation_hides_templates():
    """Protected template far from raw (rotation is not near-identity)."""
    rot = KeyedRotation(64, seed=9)
    t = jax.random.normal(jax.random.PRNGKey(2), (10, 64))
    tp = rot.protect(t)
    cos = np.diag(np.asarray(cosine_scores(t, tp)))
    assert np.all(np.abs(cos) < 0.6), cos


def test_rotation_invertible_with_key():
    rot = KeyedRotation(96, seed=4)
    t = jax.random.normal(jax.random.PRNGKey(3), (5, 96))
    back = rot.unprotect(rot.protect(t))
    np.testing.assert_allclose(np.asarray(t), np.asarray(back), atol=1e-4)


def test_stream_cipher_roundtrip_and_diffusion():
    key = jax.random.PRNGKey(42)
    data = b"subject-4711:watchlist-alpha" * 33 + b"x"
    enc = encrypt_bytes(key, data)
    assert decrypt_bytes(key, enc) == data
    # ciphertext should look nothing like plaintext
    overlap = np.mean(enc[: len(data)] == np.frombuffer(data, np.uint8))
    assert overlap < 0.05
    # wrong key fails to decrypt
    bad = decrypt_bytes(jax.random.PRNGKey(43), enc)
    assert bad != data


def test_encrypt_array_roundtrip():
    key = jax.random.PRNGKey(7)
    x = np.random.default_rng(0).normal(size=(13, 8)).astype(np.float32)
    np.testing.assert_array_equal(decrypt_array(key, encrypt_array(key, x)), x)


def test_secure_gallery_end_to_end():
    rng = np.random.default_rng(1)
    dim, n = 64, 300
    gallery = rng.normal(size=(n, dim)).astype(np.float32)
    labels = [f"id{i}" for i in range(n)]
    store = SecureGallery(dim, seed=5)
    store.enroll(gallery, labels)
    # query = noisy copies of subjects 17 and 99
    q = gallery[[17, 99]] + 0.05 * rng.normal(size=(2, dim)).astype(np.float32)
    got, scores = store.match(q, k=3)
    assert got[0, 0] == "id17" and got[1, 0] == "id99"
    assert np.all(np.diff(np.asarray(scores), axis=1) <= 1e-6)  # descending


if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(data=st.binary(min_size=0, max_size=257),
           seed=st.integers(0, 2**31 - 1))
    def test_stream_cipher_roundtrip_property(data, seed):
        """encrypt/decrypt is the identity for ANY payload: empty, odd
        (non-multiple-of-4) lengths crossing the uint32 padding path, and
        every seed."""
        key = jax.random.PRNGKey(seed)
        assert decrypt_bytes(key, encrypt_bytes(key, data)) == data

    @settings(max_examples=30, deadline=None)
    @given(n=st.integers(1, 64), seed=st.integers(0, 2**31 - 2))
    def test_stream_cipher_rekey_mismatch_property(n, seed):
        """Decrypting under a rotated key never round-trips (revocation
        actually revokes) — for any non-empty payload."""
        data = bytes(range(256))[:n] * 2
        enc = encrypt_bytes(jax.random.PRNGKey(seed), data)
        assert decrypt_bytes(jax.random.PRNGKey(seed + 1), enc) != data

    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(0, 37))
    def test_stream_cipher_ciphertext_length_is_padded(n):
        """Blob layout: payload padded to a uint32 boundary + 1 pad byte."""
        key = jax.random.PRNGKey(0)
        enc = encrypt_bytes(key, b"z" * n)
        assert len(enc) == n + ((-n) % 4) + 1


def test_gallery_rekey_revokes_but_preserves_matching():
    rng = np.random.default_rng(2)
    dim, n = 32, 100
    g = rng.normal(size=(n, dim)).astype(np.float32)
    store = SecureGallery(dim, seed=11)
    store.enroll(g, list(range(n)))
    before = store.protected_gallery()
    store.rekey(new_seed=12)
    after = store.protected_gallery()
    # protected representations change entirely...
    assert float(jnp.max(jnp.abs(before - after))) > 0.1
    # ...but matching still works
    got, _ = store.match(g[[5]], k=1)
    assert got[0, 0] == 5
