"""``PpermuteByteTransport`` in one process over a one-device host mesh:
payloads come back byte for byte through the shard_map'd ``ppermute``,
the program is built once per ``(perm, cap)``, and the exchange's spans
and counters say what moved."""

import numpy as np
import pytest

from repro.core import selftrace
from repro.distributed.sharding import HOST_AXIS, PpermuteByteTransport

SELF = [(0, 0)]


@pytest.fixture
def traced():
    """selftrace on and empty for the test; off and empty after it."""
    selftrace.reset()
    selftrace.enable()
    try:
        yield selftrace
    finally:
        selftrace.disable()
        selftrace.reset()


@pytest.fixture
def transport():
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.local_devices()[:1]), (HOST_AXIS,))
    return PpermuteByteTransport(mesh)


@pytest.mark.parametrize("payload", [
    None, b"", b"\x00", bytes(range(256)) * 3,
    b"e" * 59,          # 59 + the 5-byte header fill the 64-byte cap
    b"f" * 60,          # one byte more: the next cap
], ids=["none", "empty", "zero", "768", "cap-edge", "cap-edge+1"])
def test_payload_round_trips(transport, payload):
    assert transport.exchange(payload, SELF) == payload


def test_one_build_per_cap(traced, transport):
    small = [b"", b"a" * 10, None, b"b" * 59, b"", b"c" * 31]
    for p in small:
        assert transport.exchange(p, SELF) == p
    c = traced.snapshot()["counters"]
    assert c["exchange.builds"] == 1
    assert c["exchange.calls"] == len(small)
    assert c["exchange.payload_bytes"] == sum(len(p or b"") for p in small)
    assert c["exchange.moved_bytes"] == 64 * len(small)
    for p in (b"d" * 60, b"e" * 123, b"f" * 100):
        assert transport.exchange(p, SELF) == p
    c = traced.snapshot()["counters"]
    assert c["exchange.builds"] == 2
    assert c["exchange.moved_bytes"] == 64 * len(small) + 128 * 3
    assert c["exchange.payload_bytes"] == \
        sum(len(p or b"") for p in small) + 60 + 123 + 100


def test_exchange_spans_nest(traced, transport):
    transport.exchange(b"payload", SELF)
    spans = traced.snapshot()["spans"]
    by = {s["name"]: s for s in spans}
    assert sorted(by) == ["exchange", "exchange.lengths", "exchange.ppermute"]
    assert by["exchange"]["parent"] is None
    assert by["exchange.lengths"]["parent"] == by["exchange"]["id"]
    assert by["exchange.ppermute"]["parent"] == by["exchange"]["id"]
    assert by["exchange.lengths"]["end_ns"] <= \
        by["exchange.ppermute"]["start_ns"]


def test_off_keeps_nothing_and_still_reuses(transport):
    selftrace.disable()
    selftrace.reset()
    for p in (b"x", b"y" * 20, b"z" * 40):
        assert transport.exchange(p, SELF) == p
    assert selftrace.snapshot() == {"spans": [], "counters": {}}
    assert list(transport._built) == [(((0, 0),), 64)]


def test_empty_perm_moves_nothing(traced, transport):
    assert transport.exchange(b"x", []) is None
    assert traced.snapshot() == {"spans": [], "counters": {}}
