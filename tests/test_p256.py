"""Device-side P-256 batch verify (ops/p256.py) — parity pins.

The kernel's gate is VERDICT PARITY, not speed: every test compares the
vmapped JAX kernel's verdict list bit-for-bit against the pure-Python
fallback on the same vectors, including the r/s range rejections, the
high-s encoding, the Shamir-trick degeneracies (point at infinity,
u1 == u2 doubling), and the malformed-creator None contract. One
8-lane kernel compile (~20 s on CPU) is shared by the whole module —
keep batches at 8 or below so no second ladder size compiles.
"""

import pytest

jax = pytest.importorskip("jax")

from babble_tpu.crypto import _fallback as fb  # noqa: E402
from babble_tpu.ops import p256  # noqa: E402
from tests.test_crypto import _batch_vectors  # noqa: E402


def test_available():
    assert p256.available()


def test_device_verify_batch_parity():
    """The full mixed corpus — valid / corrupt / high-s / r range /
    malformed creator — verdict-identical to the host fallback."""
    pubs, digests, sigs, expected = _batch_vectors()
    assert fb.verify_batch(pubs, digests, sigs) == expected
    # chunks of <= 8 keep the kernel on the single compiled ladder size
    got = []
    for i in range(0, len(pubs), 8):
        got += p256.verify_batch(
            pubs[i:i + 8], digests[i:i + 8], sigs[i:i + 8])
    assert got == expected


def test_device_degeneracies():
    """d=1 (Q = G) degeneracies: r = (N - z) mod N lands the Shamir
    sum on the point at infinity (reject), r = z mod N forces
    u1 == u2 through the add formula's doubling branch."""
    from babble_tpu import crypto

    k1 = fb.key_from_seed(0)
    assert k1.d == 1
    pub = fb.pub_key_bytes(k1)
    d = crypto.sha256(b"degenerate")
    z = int.from_bytes(d, "big") % fb.N
    sigs = [((fb.N - z) % fb.N or 1, 1), (z or 1, 1)]
    expected = fb.verify_batch([pub, pub], [d, d], sigs)
    assert p256.verify_batch([pub, pub], [d, d], sigs) == expected


def test_device_padding_lanes_ignored():
    """A batch smaller than the 8-lane ladder pads with copies of lane
    0; the pad lanes' verdicts must not leak into the result."""
    key = fb.key_from_seed(77)
    pub = fb.pub_key_bytes(key)
    from babble_tpu import crypto

    d = crypto.sha256(b"lane")
    r, s = fb.sign(key, d)
    assert p256.verify_batch([pub], [d], [(r, s)]) == [True]
    assert p256.verify_batch([pub], [d], [(r, s + 1)]) == [False]


def test_ingest_routes_device_backend(monkeypatch):
    """verify_events(..., device_verify=True) routes through the
    p256 kernel and memoizes the same verdicts the host path would."""
    from babble_tpu import crypto
    from babble_tpu.hashgraph.event import Event
    from babble_tpu.node import ingest

    # Event.sign goes through the active backend: give it its own key.
    key = crypto.key_from_seed(5)
    pub = crypto.pub_key_bytes(key)
    events = []
    for i in range(3):
        ev = Event.new([b"tx-%d" % i], ["p0", "p1"], pub, i)
        ev.sign(key)
        ev._sig_ok = None  # drop sign()'s memo: force real verification
        events.append(ev)
    events[1].r = int(events[1].r) + 1  # corrupt position 1

    calls = []
    real = p256.verify_batch

    def spying(pubs, digests, sigs):
        calls.append(len(pubs))
        return real(pubs, digests, sigs)

    monkeypatch.setattr(p256, "verify_batch", spying)
    assert ingest.active_backend(True) == "device-p256"
    ingest.verify_events(events, workers=4, device_verify=True)
    assert calls == [3]
    assert [ev._sig_ok for ev in events] == [True, False, True]
