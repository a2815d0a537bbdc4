"""The executable store (``hotstuff_tpu/tpu/exe_store.py``) on the CPU:
a program built and written by one verifier and loaded by the next
gives the same verdicts, every part of the key forces a miss, a torn or
foreign entry is a miss that is rebuilt, racing writers leave one whole
entry, and the warm-up says per pad shape which it was."""

import os
import pickle
import shutil
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hotstuff_tpu.crypto import ed25519_ref as ref
from hotstuff_tpu.tpu import exe_store
from hotstuff_tpu.tpu.ed25519 import BatchVerifier

#: a pad shape of no other test, so the program is this file's alone
PAD = 8


@pytest.fixture(scope="module")
def fresh_compiles():
    """Keep this file's programs out of jax's persistent cache, so that
    every build is a backend compile: XLA:CPU cannot serialize a second
    time a program it loaded from that cache (the copy it writes lacks
    the program's functions and fails at its first call)."""
    name = "jax_persistent_cache_min_compile_time_secs"
    old = getattr(jax.config, name)
    jax.config.update(name, 1e9)
    yield
    jax.config.update(name, old)


@pytest.fixture(scope="module")
def booted(tmp_path_factory, fresh_compiles):
    """Two boots on one store at one pad shape, donation on: the first
    builds the XLA wave entry's program and writes it, the second loads
    it."""
    root = str(tmp_path_factory.mktemp("exe"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("HOTSTUFF_DONATE", "1")

        def boot():
            v = BatchVerifier(min_device_batch=0, use_pallas=False, exe_dir=root)
            v.pad_sizes = (PAD,)
            v.warmup(batch=PAD)
            assert v.donate_buffers
            return v

        return root, boot(), boot()


def mixed_batch(seed: int):
    """PAD claims from a seeded draw: valid lanes and planted failures,
    some the host refuses and some only the kernel can."""
    rng = np.random.default_rng(seed)
    msgs, pks, sigs = [], [], []
    for i in range(PAD):
        sk = rng.bytes(32)
        msg = rng.bytes(1 + i)
        msgs.append(msg)
        pks.append(ref.public_from_seed(sk))
        sigs.append(ref.sign(sk, msg))
    sigs[1] = bytes([sigs[1][0] ^ 1]) + sigs[1][1:]  # R: the kernel refuses
    sigs[3] = sigs[3][:40] + bytes([sigs[3][40] ^ 0x10]) + sigs[3][41:]
    msgs[4] = msgs[4] + b"!"  # another message
    s = int.from_bytes(sigs[6][32:], "little") + ref.L  # s >= L: the host
    sigs[6] = sigs[6][:32] + s.to_bytes(32, "little")
    want = [ref.verify(sig, pk, m) for m, pk, sig in zip(msgs, pks, sigs)]
    return msgs, pks, sigs, want


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_a_built_and_a_loaded_program_give_the_same_verdicts(booted, seed):
    _, built, loaded = booted
    assert built.warm_report[PAD]["exe"] == "built"
    assert loaded.warm_report[PAD]["exe"] == "loaded"
    msgs, pks, sigs, want = mixed_batch(seed)
    assert not all(want) and any(want)
    for v in (built, loaded, built, loaded):  # donated buffers restaged
        assert v.verify_device(msgs, pks, sigs).tolist() == want


def test_the_warm_report_says_which_and_counts_a_load_as_a_hit(booted):
    _, built, loaded = booted
    first = built.describe()["warm"][str(PAD)]
    assert first["exe"] == "built" and first["exe_ms"] > 0
    assert first["trace_s"] > 0  # traced and lowered, as before the store
    again = loaded.describe()["warm"][str(PAD)]
    assert again["exe"] == "loaded" and again["exe_ms"] > 0
    assert (again["cache_hits"], again["cache_misses"]) == (1, 0)
    assert (again["trace_s"], again["lower_s"]) == (0.0, 0.0)
    for key in ("first_call_s", "compile_or_load_s"):  # the line keeps its keys
        assert key in again


def test_the_stored_key_holds_what_decides_the_program(booted):
    root, built, _ = booted
    (name,) = os.listdir(root)
    with open(os.path.join(root, name), "rb") as f:
        key = pickle.load(f)[0]
    assert (key["entry"], key["pallas"], key["donate"]) == (
        "ed25519.wave", False, True
    )
    assert key["args"][1:] == [[[128, 20], "int32"]] * 4 + [[[PAD, 100], "uint8"]]
    assert (key["jax"], key["source"]) == (
        jax.__version__, exe_store.source_digest()
    )
    assert key["device_count"] == jax.device_count()
    assert {"jaxlib", "platform_version", "device_kind", "XLA_FLAGS",
            "LIBTPU_INIT_ARGS"} <= set(key)


def tiny(a, b):
    return a.sum() + b.astype(jnp.int32).sum()


ARGS = (np.arange(12, dtype=np.int32).reshape(4, 3), np.arange(5, dtype=np.uint8))


def build(args, donate=False):
    entry = jax.jit(tiny, donate_argnums=(1,) if donate else ())
    return lambda: entry.lower(*args).compile()


@pytest.mark.parametrize("change", ["source", "jax", "shape", "donate"])
def test_each_part_of_the_key_forces_a_miss(
    tmp_path, monkeypatch, fresh_compiles, change
):
    store = exe_store.ExecutableStore(str(tmp_path))
    args, donate = ARGS, False

    def get():
        key = exe_store.key("tiny", args, donate=donate)
        program, report = store.get(key, build(args, donate))
        assert int(program(*args)) == int(tiny(*args))
        return report["exe"]

    assert (get(), get()) == ("built", "loaded")
    if change == "source":
        monkeypatch.setattr(exe_store, "source_digest", lambda: "0" * 64)
    elif change == "jax":
        monkeypatch.setattr(jax, "__version__", jax.__version__ + ".post1")
    elif change == "shape":
        args = (np.ones((8, 3), np.int32), ARGS[1])
    else:
        donate = True
    assert (get(), get()) == ("built", "loaded")
    assert len(os.listdir(tmp_path)) == 2


def test_the_source_digest_covers_a_byte_of_every_module(tmp_path, monkeypatch):
    package = os.path.dirname(exe_store.__file__)
    for name in os.listdir(package):
        if name.endswith(".py"):
            shutil.copy(os.path.join(package, name), tmp_path)
    monkeypatch.setattr(exe_store, "__file__", str(tmp_path / "exe_store.py"))
    exe_store.source_digest.cache_clear()
    try:
        before = exe_store.source_digest()
        with open(tmp_path / "pallas_dsm.py", "ab") as f:
            f.write(b"#")
        exe_store.source_digest.cache_clear()
        assert exe_store.source_digest() != before
    finally:
        exe_store.source_digest.cache_clear()


@pytest.mark.parametrize("damage", ["truncated", "garbage", "another-key"])
def test_a_torn_or_foreign_entry_is_a_miss_and_is_rebuilt(
    tmp_path, fresh_compiles, damage
):
    store = exe_store.ExecutableStore(str(tmp_path))
    key = exe_store.key("tiny", ARGS)
    store.get(key, build(ARGS))
    path = store.path(key)
    if damage == "truncated":
        with open(path, "rb") as f:
            whole = f.read()
        with open(path, "wb") as f:
            f.write(whole[: len(whole) // 2])
    elif damage == "garbage":
        with open(path, "wb") as f:
            f.write(np.random.default_rng(3).bytes(4096))
    else:
        other = exe_store.key("tiny", ARGS, donate=True)
        store.get(other, build(ARGS, donate=True))
        shutil.copy(store.path(other), path)
    assert store.load(key) is None
    program, report = store.get(key, build(ARGS))
    assert report["exe"] == "built" and int(program(*ARGS)) == int(tiny(*ARGS))
    assert int(store.load(key)(*ARGS)) == int(tiny(*ARGS))


def test_two_writers_racing_on_one_key_leave_one_whole_entry(
    tmp_path, fresh_compiles
):
    store = exe_store.ExecutableStore(str(tmp_path))
    key = exe_store.key("tiny", ARGS)
    compiled = build(ARGS)()
    start = threading.Barrier(2)
    errors = []

    def writer():
        start.wait()
        try:
            for _ in range(20):
                store.save(key, compiled)
        except Exception as e:  # surfaced below, not lost in the thread
            errors.append(e)

    threads = [threading.Thread(target=writer) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    assert os.listdir(tmp_path) == [os.path.basename(store.path(key))]
    assert int(store.load(key)(*ARGS)) == int(tiny(*ARGS))


def test_the_store_engages_on_the_pallas_entry_or_a_given_directory(tmp_path):
    """Nothing here compiles: which store a verifier would use."""
    assert BatchVerifier(use_pallas=False).exe_store is None
    beside = BatchVerifier(use_pallas=True).exe_store
    assert beside.root == os.path.join(
        jax.config.jax_compilation_cache_dir, exe_store.SUBDIR
    )
    given = BatchVerifier(use_pallas=False, exe_dir=str(tmp_path)).exe_store
    assert given.root == str(tmp_path)


def test_the_warmup_refuses_a_program_that_answers_all_valid():
    """One forged lane a pad shape: a program whose every verdict is
    "valid" stops the boot."""

    class AllValid(BatchVerifier):
        def _run_wave(self, tables, buf, donate=False):
            return jnp.ones(buf.shape[0], bool)

    v = AllValid(min_device_batch=0, use_pallas=False)
    v.pad_sizes = (PAD,)
    with pytest.raises(RuntimeError, match=r"lanes \[4\] \(forged: 4\)"):
        v.warmup(batch=PAD)
