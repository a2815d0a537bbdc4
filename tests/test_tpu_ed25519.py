"""TPU ed25519 kernel tests: point ops and batch verification vs the
pure-Python oracle (crypto/ed25519_ref.py), incl. adversarial inputs."""

import hashlib
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hotstuff_tpu.crypto import ed25519_ref as ref
from hotstuff_tpu.tpu import curve, field as F
from hotstuff_tpu.tpu.ed25519 import BatchVerifier

rng = random.Random(99)

jadd_pt = jax.jit(curve.point_add)
jdbl_pt = jax.jit(curve.point_double)


def rand_point():
    """Random curve point = [r]B via the oracle."""
    return ref.point_mul(rng.randrange(1, ref.L), ref.B_POINT)


def to_dev_point(p):
    return tuple(jnp.asarray(v)[None, :] for v in curve.point_to_limbs(p))


def assert_same_point(dev_p, ref_p):
    x = F.int_from_limbs(jax.jit(F.canonical)(F.mul(dev_p[0], jax.jit(F.pow_inv)(dev_p[2])))[0])
    y = F.int_from_limbs(jax.jit(F.canonical)(F.mul(dev_p[1], jax.jit(F.pow_inv)(dev_p[2])))[0])
    rx, ry = ref.point_affine(ref_p)
    assert (x, y) == (rx, ry)


def test_point_add_double_matches_oracle():
    for _ in range(5):
        p, q = rand_point(), rand_point()
        assert_same_point(jadd_pt(to_dev_point(p), to_dev_point(q)), ref.point_add(p, q))
        assert_same_point(jdbl_pt(to_dev_point(p)), ref.point_double(p))
    # identity edge cases (unified formulas must handle them)
    ident = tuple(jnp.asarray(v)[None, :] for v in (
        F.limbs_from_int(0), F.limbs_from_int(1), F.limbs_from_int(1), F.limbs_from_int(0)))
    p = rand_point()
    assert_same_point(jadd_pt(to_dev_point(p), ident), p)
    assert_same_point(jadd_pt(ident, ident), ref.IDENTITY)


def _sign_many(n, msg_fn):
    items = []
    for i in range(n):
        seed = bytes([i]) * 32
        pk = ref.public_from_seed(seed)
        msg = msg_fn(i)
        items.append((msg, pk, ref.sign(seed, msg)))
    return items


@pytest.fixture(scope="module")
def verifier():
    return BatchVerifier(min_device_batch=0)  # force the kernel path


def test_batch_all_valid(verifier):
    items = _sign_many(5, lambda i: b"msg-%d" % i)
    out = verifier.verify(*map(list, zip(*items)))
    assert out.tolist() == [True] * 5


def test_batch_mixed_invalid(verifier):
    items = _sign_many(8, lambda i: b"payload-%d" % i)
    msgs, pks, sigs = map(list, zip(*items))
    expected = [True] * 8
    # corrupt signature R
    sigs[1] = bytes([sigs[1][0] ^ 1]) + sigs[1][1:]; expected[1] = False
    # corrupt s half
    sigs[2] = sigs[2][:40] + bytes([sigs[2][40] ^ 0x80]) + sigs[2][41:]; expected[2] = False
    # wrong message
    msgs[3] = b"tampered"; expected[3] = False
    # wrong key
    pks[4] = ref.public_from_seed(b"\xaa" * 32); expected[4] = False
    # non-canonical s (s + L)
    s_int = int.from_bytes(sigs[5][32:], "little") + ref.L
    sigs[5] = sigs[5][:32] + s_int.to_bytes(32, "little"); expected[5] = False
    # undecompressable pubkey (y >= p encodes no point)
    pks[6] = (ref.P + 1).to_bytes(32, "little"); expected[6] = False
    out = verifier.verify(msgs, pks, sigs)
    assert out.tolist() == expected
    # agreement with the oracle on every item
    for got, (m, pk, sig) in zip(out.tolist(), zip(msgs, pks, sigs)):
        assert got == ref.verify(sig, pk, m)


def test_rfc_vectors_on_device(verifier):
    vecs = [
        ("9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60", ""),
        ("4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb", "72"),
        ("c5aa8df43f9f837bedb7442f31dcb7b166d38535076f094b85ce3a2e0b4458f7", "af82"),
    ]
    msgs, pks, sigs = [], [], []
    for seed_hex, msg_hex in vecs:
        seed, msg = bytes.fromhex(seed_hex), bytes.fromhex(msg_hex)
        msgs.append(msg)
        pks.append(ref.public_from_seed(seed))
        sigs.append(ref.sign(seed, msg))
    assert verifier.verify(msgs, pks, sigs).tolist() == [True] * 3


def test_qc_shape_shared_message(verifier):
    """The QC-verify shape: many signers, one digest."""
    digest = hashlib.sha512(b"block").digest()[:32]
    msgs, pks, sigs = [], [], []
    for i in range(7):
        seed = bytes([0x40 + i]) * 32
        msgs.append(digest)
        pks.append(ref.public_from_seed(seed))
        sigs.append(ref.sign(seed, digest))
    assert verifier.verify(msgs, pks, sigs).all()
    sigs[3] = sigs[3][:10] + b"\x00" + sigs[3][11:]
    out = verifier.verify(msgs, pks, sigs)
    assert out.tolist() == [True, True, True, False, True, True, True]


def test_committee_precompute_cache(verifier):
    pks = [ref.public_from_seed(bytes([i]) * 32) for i in range(4)]
    verifier.precompute(pks)
    assert all(pk in verifier._point_cache for pk in pks)


def test_warmup_leaves_the_key_table_at_its_production_size():
    """A padded wave brings the pad claim's key.  The warm-up puts it in
    the point cache before it compiles anything, so the staged table,
    whose row count is a shape of the jitted entry's argument, does not
    grow under the first production wave and the entry does not compile
    again at every pad shape inside that wave's deadline."""
    from hotstuff_tpu.crypto.async_service import make_pad_claim
    from hotstuff_tpu.tpu import ed25519 as device

    v = BatchVerifier(min_device_batch=0)
    committee = _sign_many(7, lambda i: b"a proposal")
    v.precompute([pk for _, pk, _ in committee])
    v.warmup(batch=8)
    rows = len(v._tables[1])
    entry = device._wave_entry(v.use_pallas, v.donate_buffers)
    compiled = entry._cache_size()
    _, digest, pk, sig = make_pad_claim()
    msgs, pks, sigs = map(list, zip(*committee))
    # the wave as the service pads it: real claims, then pad claims
    out = v.verify_device(msgs + [digest] * 9, pks + [pk] * 9, sigs + [sig] * 9)
    assert out.all()
    assert len(v._tables[1]) == rows
    assert entry._cache_size() == compiled


def test_pallas_dsm_parity_interpret():
    """The Pallas double-scalar-mult kernel (tpu/pallas_dsm.py) must agree
    with the XLA path bit-for-bit.  Runs in interpreter mode so the
    parity check works on the CPU test mesh; on-device coverage comes
    from the benchmark and the TPU rig."""
    from hotstuff_tpu.tpu import pallas_dsm
    from hotstuff_tpu.tpu.ed25519 import _bytes_to_windows_msb

    B = pallas_dsm.LANE_TILE  # minimum lane-aligned batch
    s_rows = np.stack(
        [
            np.frombuffer(
                rng.randrange(ref.L).to_bytes(32, "little"), np.uint8
            )
            for _ in range(B)
        ]
    )
    k_rows = np.stack(
        [
            np.frombuffer(
                rng.randrange(ref.L).to_bytes(32, "little"), np.uint8
            )
            for _ in range(B)
        ]
    )
    s_win = jnp.asarray(_bytes_to_windows_msb(s_rows).T)
    k_win = jnp.asarray(_bytes_to_windows_msb(k_rows).T)
    pts = [rand_point() for _ in range(B)]
    a_point = tuple(
        jnp.asarray(np.stack([curve.point_to_limbs(p)[c] for p in pts]))
        for c in range(4)
    )

    x_out = curve.dual_scalar_mult(s_win, k_win, a_point)
    p_out = pallas_dsm.dual_scalar_mult(s_win, k_win, a_point, interpret=True)
    canon = jax.jit(F.canonical)
    # X, Y, Z only: the pallas kernel's need_t schedule leaves T
    # uncomputed (compressed_equals never reads it)
    for xla, pal in list(zip(x_out, p_out))[:3]:
        assert (np.asarray(canon(xla)) == np.asarray(canon(pal))).all()


def test_pallas_fused_epilogue_parity_interpret():
    """The in-kernel compressed-equality epilogue (limb-major ports of
    _chain/_strict/canonical/pow_inv) against the XLA field ops: encode
    the XLA scan's outputs host-side, corrupt the sign on some lanes and
    the y encoding on others, and check the fused unsplit kernel's
    verdict lane-by-lane."""
    import jax.numpy as jnp

    from hotstuff_tpu.tpu import pallas_dsm
    from hotstuff_tpu.tpu.ed25519 import _bytes_to_windows_msb

    B = pallas_dsm.LANE_TILE
    s_rows = np.stack(
        [
            np.frombuffer(
                rng.randrange(ref.L).to_bytes(32, "little"), np.uint8
            )
            for _ in range(B)
        ]
    )
    k_rows = np.stack(
        [
            np.frombuffer(
                rng.randrange(ref.L).to_bytes(32, "little"), np.uint8
            )
            for _ in range(B)
        ]
    )
    s_win = jnp.asarray(_bytes_to_windows_msb(s_rows).T)
    k_win = jnp.asarray(_bytes_to_windows_msb(k_rows).T)
    pts = [rand_point() for _ in range(B)]
    a_point = tuple(
        jnp.asarray(np.stack([curve.point_to_limbs(p)[c] for p in pts]))
        for c in range(4)
    )

    # the true compressed encodings, via the XLA path
    X, Y, Z, _ = curve.dual_scalar_mult(s_win, k_win, a_point)
    zinv = jax.jit(F.pow_inv)(Z)
    y_can = np.asarray(jax.jit(F.canonical)(F.mul(Y, zinv)))
    x_can = np.asarray(jax.jit(F.canonical)(F.mul(X, zinv)))
    r_y = y_can.copy()
    r_sign = (x_can[:, 0] & 1).astype(np.int32)
    expect = np.ones(B, bool)
    r_sign[:8] ^= 1  # wrong sign bit
    r_y[8:16, 0] ^= 1  # wrong y encoding
    expect[:16] = False

    ok = np.asarray(
        pallas_dsm.verify_compressed(
            s_win,
            k_win,
            a_point,
            jnp.asarray(r_y),
            jnp.asarray(r_sign),
            interpret=True,
        )
    )
    assert ok.tolist() == expect.tolist()


def test_donate_buffers_env_gate(monkeypatch):
    """HOTSTUFF_DONATE forces buffer donation on/off; unset defers to
    the backend platform (accelerators donate, CPU jax would warn)."""
    monkeypatch.setenv("HOTSTUFF_DONATE", "0")
    assert not BatchVerifier(min_device_batch=0).donate_buffers
    monkeypatch.setenv("HOTSTUFF_DONATE", "1")
    assert BatchVerifier(min_device_batch=0).donate_buffers
    monkeypatch.delenv("HOTSTUFF_DONATE")
    v = BatchVerifier(min_device_batch=0)
    assert v.donate_buffers == (jax.default_backend() in ("tpu", "gpu"))


def test_donated_dispatch_verdict_parity(monkeypatch):
    """With donation forced on, staging buffers are consumed per wave —
    and because verify() restages every wave, back-to-back waves of
    different shapes (and a repeat of the first) keep exact verdict
    parity.  The committee gather source (args 0-3) is NOT donated, so
    the epoch-static key tables survive every wave."""
    monkeypatch.setenv("HOTSTUFF_DONATE", "1")
    v = BatchVerifier(min_device_batch=0)
    assert v.donate_buffers
    items = _sign_many(6, lambda i: b"donate-%d" % i)
    msgs, pks, sigs = map(list, zip(*items))
    sigs[2] = bytes([sigs[2][0] ^ 1]) + sigs[2][1:]
    expected = [True, True, False, True, True, True]
    assert v.verify(msgs, pks, sigs).tolist() == expected
    # a different wave shape in between...
    items2 = _sign_many(3, lambda i: b"other-%d" % i)
    assert v.verify(*map(list, zip(*items2))).tolist() == [True] * 3
    # ...then the first wave again: donation corrupted nothing cached
    assert v.verify(msgs, pks, sigs).tolist() == expected


def test_challenge_hash_memo():
    """The per-(sig, pk, msg) challenge-hash memo serves repeated rows
    (pad claims, re-verified certificates) without re-hashing — and
    never changes a verdict."""
    v = BatchVerifier(min_device_batch=0)
    items = _sign_many(4, lambda i: b"memo-%d" % i)
    msgs, pks, sigs = map(list, zip(*items))
    assert v.verify(msgs, pks, sigs).all()
    assert len(v._challenge_memo) == 4
    assert v.verify(msgs, pks, sigs).all()  # served from the memo


def test_stage_routing_thresholds():
    """stage() contract after the split-kernel deletion: every batch
    goes through prepare() to _run_wave (overridden by the
    mesh-sharded subclass); use_pallas only changes which kernel
    _run_wave dispatches.  What to call, with what (the device-resident
    tables and the wave's one buffer at its pad shape), and the host's
    validity."""
    from hotstuff_tpu.tpu.ed25519 import WAVE_COLS

    items = _sign_many(3, lambda i: b"route-%d" % i)
    msgs, pks, sigs = map(list, zip(*items))

    for use_pallas in (True, False):
        v = BatchVerifier(min_device_batch=0, use_pallas=use_pallas)
        kernel, args, valid = v.stage(msgs, pks, sigs)
        assert kernel == v._run_wave
        tables, buf = args
        assert valid.all() and len(tables) == 4
        assert buf.dtype == np.uint8
        padded = next(p for p in v._padded_sizes() if p >= len(msgs))
        assert buf.shape == (padded, WAVE_COLS)
    # the staged arguments are the kernel's: called as stage() hands
    # them out (no donation), twice over the same buffer
    assert np.asarray(kernel(*args))[:3].all()
    assert np.asarray(kernel(*args))[:3].all()


# ---- a wave as one buffer in one jitted call (ISSUE 38) -------------------


def _edge_rows():
    """Staged rows whose decomposition has somewhere to go wrong: all
    zero, all ones, s = L - 1 with k = L - 1, and an R with bit 255 set
    over a y of alternating bits."""
    from hotstuff_tpu.tpu.ed25519 import K_COLS, WAVE_COLS

    rows = np.zeros((4, WAVE_COLS), np.uint8)
    rows[1] = 0xFF
    top = np.frombuffer((ref.L - 1).to_bytes(32, "little"), np.uint8)
    rows[2, 32:64] = top
    rows[2, K_COLS] = top
    rows[3, :32] = 0x55
    rows[3, 31] |= 0x80
    return rows


@pytest.mark.parametrize("padded", [1, 4, 16, 64, 128, 256, 1024, 4096])
def test_device_side_decomposition_matches_the_numpy_reference(padded):
    """``unpack_wave`` (shifts and masks inside the jitted call) against
    the plain numpy decomposition the host did until PR 38, bit for bit,
    at every pad shape of both grids: windows of s and k, R's limbs and
    sign, and the key rows gathered by the staged index."""
    from hotstuff_tpu.tpu.ed25519 import (
        K_COLS,
        PAD_SIZES,
        PALLAS_PAD_SIZES,
        ROW_COLS,
        _bytes_rows_to_limbs,
        _bytes_to_windows_msb,
        unpack_wave,
    )

    assert padded in PAD_SIZES + PALLAS_PAD_SIZES
    gen = np.random.default_rng(padded)
    buf = gen.integers(0, 256, (padded, 100), dtype=np.uint8)
    edge = _edge_rows()[:padded]
    buf[: len(edge)] = edge
    n_keys = 300  # more than a byte of row index holds
    idx = gen.integers(0, n_keys, padded).astype("<u4")
    buf[:, ROW_COLS] = idx.view(np.uint8).reshape(padded, 4)
    tables = tuple(
        gen.integers(0, 1 << F.LIMB_BITS, (n_keys, F.NLIMBS), dtype=np.int32)
        for _ in range(4)
    )

    out = jax.jit(unpack_wave)(tables, buf)
    ax, ay, az, at, s_win, k_win, r_y, r_sign = map(np.asarray, out)
    assert s_win.shape == k_win.shape == (curve.NWIN, padded)
    assert all(a.dtype == np.int32 for a in (s_win, k_win, r_y, r_sign, ax))
    np.testing.assert_array_equal(s_win, _bytes_to_windows_msb(buf[:, 32:64]).T)
    np.testing.assert_array_equal(k_win, _bytes_to_windows_msb(buf[:, K_COLS]).T)
    np.testing.assert_array_equal(r_y, _bytes_rows_to_limbs(buf[:, :32]))
    np.testing.assert_array_equal(r_sign, buf[:, 31] >> 7)
    for got, table in zip((ax, ay, az, at), tables):
        np.testing.assert_array_equal(got, table[idx])


def _mixed_wave():
    """Rows the host refuses, rows the kernel refuses, rows that pass and
    the service's pad rows: (messages, keys, signatures, what each is)."""
    from hotstuff_tpu.crypto.async_service import make_pad_claim

    # 32-byte digests, as a claim's message is (an arena holds no other)
    items = _sign_many(9, lambda i: hashlib.sha512(b"wave-%d" % i).digest()[:32])
    msgs, pks, sigs = map(list, zip(*items))
    kinds = ["valid"] * 9
    msgs[1] = msgs[0]; kinds[1] = "wrong message"
    pks[2] = ref.public_from_seed(b"\xbb" * 32); kinds[2] = "wrong key"
    s_int = int.from_bytes(sigs[3][32:], "little") + ref.L
    sigs[3] = sigs[3][:32] + s_int.to_bytes(32, "little"); kinds[3] = "s >= L"
    pks[4] = (ref.P + 1).to_bytes(32, "little"); kinds[4] = "key off the curve"
    sigs[5] = sigs[5][:63]; kinds[5] = "short signature"
    pks[6] = pks[6] + b"\x00"; kinds[6] = "long key"
    sigs[7] = bytes([sigs[7][0] ^ 4]) + sigs[7][1:]; kinds[7] = "wrong R"
    _, digest, pk, sig = make_pad_claim()
    pad = 16 - len(msgs)
    return (
        msgs + [digest] * pad, pks + [pk] * pad, sigs + [sig] * pad,
        kinds + ["pad"] * pad,
    )


def _oracle(msgs, pks, sigs):
    return [
        len(sig) == 64 and len(pk) == 32 and ref.verify(sig, pk, m)
        for m, pk, sig in zip(msgs, pks, sigs)
    ]


def test_mixed_wave_verdicts_match_the_oracle(verifier):
    msgs, pks, sigs, kinds = _mixed_wave()
    want = _oracle(msgs, pks, sigs)
    assert want == [k in ("valid", "pad") for k in kinds]
    got = verifier.verify_device(msgs, pks, sigs).tolist()
    assert got == want, [k for k, g, w in zip(kinds, got, want) if g != w]
    # what the host refused never reached the kernel as itself: its row
    # went as a pad row, whose lane passes, and valid_host decides
    valid_host, (_, buf) = verifier.prepare(msgs, pks, sigs)
    for i, kind in enumerate(kinds):
        refused = kind in ("s >= L", "key off the curve", "short signature",
                           "long key")
        assert valid_host[i] == (not refused), kind
        if refused:
            assert buf[i].tolist() == [1] + [0] * 99, kind


def test_verify_packed_and_verify_device_agree_row_for_row(verifier):
    """The arena path and the claim path fill the same buffer through
    the same tail: the same verdicts, and the same staged bytes, over
    the rows an arena can hold (the wire parser refuses wrong lengths)."""
    msgs, pks, sigs, kinds = _mixed_wave()
    keep = [i for i, k in enumerate(kinds) if "short" not in k and "long" not in k]
    msgs, pks, sigs = ([col[i] for i in keep] for col in (msgs, pks, sigs))
    rows = len(keep)
    cols = (b"".join(msgs), b"".join(pks), b"".join(sigs))

    by_claim = verifier.verify_device(msgs, pks, sigs)
    by_arena = verifier.verify_packed(*cols, rows)
    assert by_arena.tolist() == by_claim.tolist() == _oracle(msgs, pks, sigs)
    _, (_, buf_claim) = verifier.prepare(msgs, pks, sigs)
    staged = buf_claim.copy()  # the scratch is reused by the next fill
    _, (_, buf_arena) = verifier.prepare_packed(
        *(
            np.frombuffer(c, np.uint8).reshape(rows, w)
            for c, w in zip(cols, (32, 32, 64))
        )
    )
    np.testing.assert_array_equal(buf_arena, staged)


def test_a_wave_is_one_host_array_and_one_jitted_call(verifier):
    """h2d and calls advance by one a backend call, and nothing else
    crosses to the device: with implicit transfers disallowed only the
    one explicit device_put is left."""
    msgs, pks, sigs, _ = _mixed_wave()
    want = _oracle(msgs, pks, sigs)
    assert verifier.verify_device(msgs, pks, sigs).tolist() == want  # warm
    h2d, calls = verifier.h2d, verifier.calls
    with jax.transfer_guard("disallow"):
        for k in range(1, 4):
            assert verifier.verify_device(msgs, pks, sigs).tolist() == want
            assert (verifier.h2d - h2d, verifier.calls - calls) == (k, k)
    # a new key restages the four tables, once, and still one call a wave
    items = _sign_many(2, lambda i: b"stranger")
    stranger = ref.public_from_seed(b"\xcc" * 32)
    out = verifier.verify_device(
        [m for m, _, _ in items], [stranger, items[1][1]], [s for _, _, s in items]
    )
    assert out.tolist() == [False, True]
    assert (verifier.h2d - h2d, verifier.calls - calls) == (3 + 4 + 1, 4)
    # an oversized batch is one array and one call a chunk
    v = BatchVerifier(min_device_batch=0, use_pallas=False)
    v.pad_sizes = (4, 16)
    out = v.verify_device(*(col[:13] + col[:13] for col in (msgs, pks, sigs)))
    assert out.tolist() == want[:13] * 2
    assert (v.h2d, v.calls) == (4 + 2, 2)


def test_after_warmup_the_first_wave_at_each_bucket_compiles_nothing():
    """The warm-up goes through the one jitted entry at every pad shape a
    wave can land on; a first production wave at each of them, and a
    stranger's key in one, leave the entry's cache as the warm-up did."""
    from hotstuff_tpu.crypto.async_service import make_pad_claim
    from hotstuff_tpu.tpu import ed25519 as device

    v = BatchVerifier(min_device_batch=0)
    committee = _sign_many(7, lambda i: b"a proposal")
    v.precompute([pk for _, pk, _ in committee])
    v.warmup(batch=8)
    entry = device._wave_entry(v.use_pallas, v.donate_buffers)
    compiled = entry._cache_size()
    assert sorted(v.warm_report) == [1, 4, 16]
    _, digest, pk, sig = make_pad_claim()
    msgs, pks, sigs = map(list, zip(*committee))
    msgs[0] = b"from a key the committee has not seen"
    pks[0] = ref.public_from_seed(b"\xdd" * 32)
    for bucket in sorted(v.warm_report):
        real = min(bucket, len(msgs))
        pad = bucket - real
        out = v.verify_device(
            msgs[:real] + [digest] * pad,
            pks[:real] + [pk] * pad,
            sigs[:real] + [sig] * pad,
        )
        assert out.tolist() == [False] + [True] * (bucket - 1)
        assert entry._cache_size() == compiled, bucket


@pytest.mark.parametrize("rows", [0, 1, 126, 127, 128, 300])
def test_the_key_table_grows_by_doubling_from_128_rows(rows):
    """The table's row count is a shape of the jitted entry's argument:
    row 0 and the keys, held at 128 rows until they no longer fit."""
    v = BatchVerifier(min_device_batch=0)
    point = curve.point_to_limbs(ref.point_neg(ref.B_POINT))
    for i in range(rows):
        v._point_cache[i.to_bytes(32, "little")] = point
    v._point_cache[b"\xff" * 32] = None  # decompresses to no point: no row
    tables, row_of = v._rebuild_tables()
    want = 128
    while want < rows + 1:
        want *= 2
    assert {t.shape for t in tables} == {(want, F.NLIMBS)}
    assert sorted(row_of.values()) == list(range(1, rows + 1))
    assert not tables[1][0].any() and (tables[1][1 : rows + 1] == point[1]).all()


def test_stats_line_carries_the_backends_counters(verifier, monkeypatch, caplog):
    """A device-routed wave through the service: its stats line ends in
    ``h2d=`` and ``calls=``, the device verifier's own cumulative counts
    as the host forwards them, and over the waves of this test both
    advance by ``chunks``."""
    import asyncio
    import logging
    import re

    from hotstuff_tpu.crypto.async_service import AsyncVerifyService
    from hotstuff_tpu.node.node import LazyDeviceVerifier, _DeviceDispatch

    monkeypatch.setenv("HOTSTUFF_FORCE_DEVICE_ROUTE", "1")
    kind = "tpu"
    monkeypatch.setattr(LazyDeviceVerifier, "_shared_device", {kind: verifier})
    monkeypatch.setattr(
        LazyDeviceVerifier, "_shared_dispatch", {kind: _DeviceDispatch(verifier)}
    )
    monkeypatch.setattr(LazyDeviceVerifier, "_warm", {kind})
    host = LazyDeviceVerifier(kind)
    assert host.device_counters() == (verifier.h2d, verifier.calls)
    # a host whose device has not materialized has handed over nothing
    assert LazyDeviceVerifier("tpu-sharded").device_counters() == (0, 0)

    msgs, pks, sigs, _ = _mixed_wave()
    verifier.verify_device(msgs, pks, sigs)  # the bucket's shape, warm
    claims = [("one", m, pk, sig) for m, pk, sig in zip(msgs[:3], pks, sigs)]

    async def drive():
        service = AsyncVerifyService(host, device=True)
        try:
            h2d, calls = verifier.h2d, verifier.calls
            for _ in range(3):
                assert await service.verify_claims(claims) == [
                    True, False, False,
                ]
            service._next_stats_log = 0.0
            service._log_stats()
            return service.chunks, verifier.h2d - h2d, verifier.calls - calls
        finally:
            service.close()

    with caplog.at_level(logging.INFO, logger="hotstuff_tpu.crypto.async_service"):
        chunks, h2d, calls = asyncio.run(drive())
    assert chunks == h2d == calls == 3
    line = [m for m in caplog.messages if m.startswith("Verify service stats")][-1]
    counters = dict(re.findall(r"(\w+)=(\d+)(?= |$)", line))
    assert int(counters["chunks"]) == 3
    assert line.endswith(f"h2d={verifier.h2d} calls={verifier.calls}")
