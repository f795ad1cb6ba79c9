"""Data plane: token file round-trip, deterministic sharded loading,
exact resume, prefetch equivalence, device placement on the test mesh."""

import numpy as np
import pytest

from tony_tpu.data import (
    PrefetchLoader,
    ShardedBatchLoader,
    TokenDataset,
    device_put_sharded_batch,
    write_tokens,
)


def _toy_dataset(n=4096, vocab=1000, seed=0):
    rng = np.random.default_rng(seed)
    return TokenDataset.from_array(rng.integers(0, vocab, size=n))


def test_token_file_round_trip(tmp_path):
    path = tmp_path / "corpus.bin"
    write_tokens(path, np.arange(1000) % 7)
    write_tokens(path, np.arange(5))  # append
    ds = TokenDataset.from_bin(path)
    assert len(ds) == 1005
    np.testing.assert_array_equal(ds.window(0, 7), np.arange(7) % 7)
    np.testing.assert_array_equal(ds.window(1000, 5), np.arange(5))
    assert ds.window(0, 3).dtype == np.int32


def test_token_file_uint32_and_range_check(tmp_path):
    with pytest.raises(ValueError, match="uint32"):
        write_tokens(tmp_path / "x.bin", [70000], dtype=np.uint16)
    path = write_tokens(tmp_path / "big.bin", [70000, 1], dtype=np.uint32)
    ds = TokenDataset.from_bin(path)
    np.testing.assert_array_equal(ds.window(0, 2), [70000, 1])


def test_token_file_rejects_garbage(tmp_path):
    p = tmp_path / "bad.bin"
    p.write_bytes(b"not a token file at all")
    with pytest.raises(ValueError, match="token file"):
        TokenDataset.from_bin(p)


def test_loader_shapes_and_target_shift():
    ds = _toy_dataset()
    loader = ShardedBatchLoader(ds, global_batch=8, seq_len=32)
    x, y = next(loader)
    assert x.shape == (8, 32) and y.shape == (8, 32)
    # targets are inputs shifted by one within each window
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])


def test_loader_is_deterministic_in_seed_and_step():
    ds = _toy_dataset()
    a = ShardedBatchLoader(ds, 8, 32, seed=7)
    b = ShardedBatchLoader(ds, 8, 32, seed=7)
    for _ in range(5):
        (xa, ya), (xb, yb) = next(a), next(b)
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)
    c = ShardedBatchLoader(ds, 8, 32, seed=8)
    assert not np.array_equal(next(c)[0], ShardedBatchLoader(ds, 8, 32, seed=7).batch_at(0)[0])


def test_loader_epoch_reshuffles_but_covers_everything():
    ds = _toy_dataset(n=8 * 32 * 4 + 1)  # exactly 4 steps/epoch
    loader = ShardedBatchLoader(ds, 8, 32, seed=1)
    assert loader.steps_per_epoch == 4

    def epoch_rows(epoch):
        rows = []
        for i in range(4):
            x, _ = loader.batch_at(epoch * 4 + i)
            rows.append(x)
        return np.concatenate(rows)

    e0, e1 = epoch_rows(0), epoch_rows(1)
    # same multiset of windows (sort rows lexicographically), different order
    assert not np.array_equal(e0, e1)
    np.testing.assert_array_equal(
        np.sort(e0.view([("", e0.dtype)] * e0.shape[1]), axis=0),
        np.sort(e1.view([("", e1.dtype)] * e1.shape[1]), axis=0),
    )


def test_loader_process_shards_partition_global_batch():
    ds = _toy_dataset()
    whole = ShardedBatchLoader(ds, 8, 16, seed=3)
    shards = [
        ShardedBatchLoader(ds, 8, 16, seed=3, process_index=p, process_count=4)
        for p in range(4)
    ]
    gx, _ = whole.batch_at(2)
    parts = [s.batch_at(2)[0] for s in shards]
    assert all(p.shape == (2, 16) for p in parts)
    # interleaved reassembly p::4 recovers the global batch exactly
    rebuilt = np.empty_like(gx)
    for p, part in enumerate(parts):
        rebuilt[p::4] = part
    np.testing.assert_array_equal(rebuilt, gx)


def test_loader_resume_is_exact():
    ds = _toy_dataset()
    loader = ShardedBatchLoader(ds, 8, 32, seed=5)
    stream = [next(loader) for _ in range(6)]
    state = None
    loader2 = ShardedBatchLoader(ds, 8, 32, seed=5)
    for _ in range(3):
        next(loader2)
    state = loader2.state()
    resumed = ShardedBatchLoader(ds, 8, 32, seed=5)
    resumed.restore(state)
    for i in range(3, 6):
        x, y = next(resumed)
        np.testing.assert_array_equal(x, stream[i][0])
        np.testing.assert_array_equal(y, stream[i][1])
    with pytest.raises(ValueError, match="seed"):
        ShardedBatchLoader(ds, 8, 32, seed=6).restore(state)


def test_loader_validates_sizes():
    ds = _toy_dataset(n=100)
    with pytest.raises(ValueError, match="divisible"):
        ShardedBatchLoader(ds, 8, 16, process_count=3)
    with pytest.raises(ValueError, match="windows"):
        ShardedBatchLoader(ds, 8, 16)  # only 6 windows of 16 fit in 100


def test_prefetch_matches_sync_and_propagates_errors():
    ds = _toy_dataset()
    sync = ShardedBatchLoader(ds, 8, 32, seed=2)
    pre = PrefetchLoader(ShardedBatchLoader(ds, 8, 32, seed=2))
    for _ in range(5):
        (xs, ys), (xp, yp) = next(sync), next(pre)
        np.testing.assert_array_equal(xs, xp)
        np.testing.assert_array_equal(ys, yp)
    pre.close()

    def boom():
        yield (np.zeros(1), np.zeros(1))
        raise RuntimeError("disk on fire")

    it = PrefetchLoader(boom())
    next(it)
    with pytest.raises(RuntimeError, match="disk on fire"):
        next(it)


def test_device_put_sharded_batch_on_mesh():
    import jax
    from tony_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=2, fsdp=4))
    ds = _toy_dataset()
    loader = ShardedBatchLoader(ds, 8, 32)
    x, y = next(loader)
    gx, gy = device_put_sharded_batch((x, y), mesh)
    assert gx.shape == (8, 32)
    assert not gx.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(gx), x)
    # feeds straight into a jitted mean without resharding errors
    assert np.isfinite(float(jax.jit(lambda a: a.astype(np.float32).mean())(gx)))


def test_lm_train_example_consumes_token_file(tmp_path):
    """lm_train --data end-to-end on the CPU mesh: real loader feeding the
    sharded train step, metrics written, loss finite."""
    import json
    from tony_tpu.examples import lm_train

    rng = np.random.default_rng(0)
    path = write_tokens(tmp_path / "corpus.bin", rng.integers(0, 256, size=20000))
    out = tmp_path / "m.json"
    rc = lm_train.main([
        "--steps", "3", "--batch-size", "8", "--seq-len", "32",
        "--vocab", "256", "--d-model", "32", "--n-layers", "1",
        "--n-heads", "2", "--d-ff", "64", "--dtype", "float32",
        "--mesh", "data=2,fsdp=4", "--data", str(path),
        "--metrics-out", str(out),
    ])
    assert rc == 0
    metrics = json.loads(out.read_text())
    assert np.isfinite(metrics["final_loss"])
    assert metrics["mesh"]["data"] == 2 and metrics["mesh"]["fsdp"] == 4
    # every result names the device that produced it
    assert metrics["device"]["platform"] == "cpu"
    assert metrics["device"]["count"] == 8 and metrics["device"]["kind"]


def test_lm_train_resumes_onto_the_step_shardings(tmp_path, capsys):
    """A second run with the same --checkpoint-dir resumes: the saved state
    restores from shapes alone straight onto the mesh shardings the step
    expects (the fresh initialisation is dropped first — one chip does not
    hold two copies at a realistic size), and training goes on from the
    step after the save."""
    import json

    from tony_tpu.examples import lm_train

    args = ["--batch-size", "8", "--seq-len", "16", "--vocab", "64",
            "--d-model", "32", "--n-layers", "1", "--n-heads", "2",
            "--d-ff", "64", "--dtype", "float32", "--mesh", "data=2,fsdp=4",
            "--checkpoint-dir", str(tmp_path / "ck"),
            "--checkpoint-every", "100"]
    out = tmp_path / "m.json"
    assert lm_train.main(["--steps", "3", *args]) == 0
    capsys.readouterr()
    assert lm_train.main(["--steps", "18", "--metrics-out", str(out),
                          *args]) == 0
    printed = capsys.readouterr().out
    assert "resumed from checkpoint step 2" in printed
    assert "step 20: loss" in printed        # steps 3..20, not 0..17
    assert np.isfinite(json.loads(out.read_text())["final_loss"])


def test_append_uses_file_header_dtype(tmp_path):
    """Appending to an existing file must honor the header dtype (mixing
    widths would corrupt the memmap) and range-check against it."""
    path = write_tokens(tmp_path / "c.bin", [1, 2, 3])  # uint16 header
    write_tokens(path, [4, 5], dtype=np.uint32)  # coerced to file's uint16
    ds = TokenDataset.from_bin(path)
    np.testing.assert_array_equal(ds.window(0, 5), [1, 2, 3, 4, 5])
    with pytest.raises(ValueError, match="uint16"):
        write_tokens(path, [70000], dtype=np.uint32)


def test_prefetch_terminal_state_does_not_hang():
    """After StopIteration/error, further next() calls must re-raise
    immediately instead of blocking on an empty queue forever."""
    it = PrefetchLoader(iter([(np.zeros(1), np.zeros(1))]))
    next(it)
    for _ in range(3):
        with pytest.raises(StopIteration):
            next(it)

    def boom():
        raise RuntimeError("dead disk")
        yield  # pragma: no cover

    bad = PrefetchLoader(boom())
    for _ in range(3):
        with pytest.raises(RuntimeError, match="dead disk"):
            next(bad)


def test_prefetch_state_counts_consumed_not_produced():
    """The producer runs ahead; PrefetchLoader.state() must reflect batches
    the consumer actually saw so checkpoint/restore doesn't skip data."""
    import time as _time

    ds = _toy_dataset()
    inner = ShardedBatchLoader(ds, 8, 32, seed=4)
    pre = PrefetchLoader(inner, depth=2)
    consumed = [next(pre) for _ in range(3)]
    _time.sleep(0.2)  # let the producer run ahead
    assert inner.step > 3  # producer genuinely ahead
    state = pre.state()
    assert state["step"] == 3
    pre.close()

    resumed = ShardedBatchLoader(ds, 8, 32, seed=4)
    resumed.restore(state)
    x_next, _ = next(resumed)
    # the first batch after restore is the first one the consumer never saw
    follow = ShardedBatchLoader(ds, 8, 32, seed=4)
    expected = follow.batch_at(3)[0]
    np.testing.assert_array_equal(x_next, expected)
    np.testing.assert_array_equal(consumed[0][0], follow.batch_at(0)[0])


def test_loader_shard_info_and_seed_validation(tmp_path):
    from tony_tpu.parallel import MeshSpec, build_mesh
    from tony_tpu.data import loader_shard_info

    seq_mesh = build_mesh(MeshSpec(fsdp=1, seq=8))
    assert loader_shard_info(seq_mesh, 2, 4) == (0, 1)  # replicated contract
    dp_mesh = build_mesh(MeshSpec(data=2, fsdp=4))
    assert loader_shard_info(dp_mesh, 2, 4) == (2, 4)
    with pytest.raises(ValueError, match="seed"):
        ShardedBatchLoader(_toy_dataset(), 8, 32, seed=-1)


def test_seq_sharded_loader_contents():
    """Sequence shards concatenate bit-for-bit into the unsharded batch,
    and each shard reads only its slice — the data-plane half of ring/
    Ulysses SP at context lengths a host can't (or shouldn't) load whole."""
    ds = _toy_dataset()
    full = ShardedBatchLoader(ds, 8, 32, seed=7)
    fx, fy = full.batch_at(5)
    C = 4
    shards = [
        ShardedBatchLoader(ds, 8, 32, seed=7,
                           seq_shard_index=s, seq_shard_count=C)
        for s in range(C)
    ]
    parts = [sh.batch_at(5) for sh in shards]
    for s, (px, py) in enumerate(parts):
        assert px.shape == (8, 8)  # local_seq = 32/4
        np.testing.assert_array_equal(px, fx[:, s * 8:(s + 1) * 8])
        np.testing.assert_array_equal(py, fy[:, s * 8:(s + 1) * 8])
    np.testing.assert_array_equal(
        np.concatenate([p[0] for p in parts], axis=1), fx
    )
    np.testing.assert_array_equal(
        np.concatenate([p[1] for p in parts], axis=1), fy
    )
    # resume state round-trips the seq-shard addressing, and a mismatch is
    # rejected (it would silently change the stream)
    st = shards[1].state()
    shards[1].restore(st)
    with pytest.raises(ValueError, match="seq_shard_index"):
        shards[2].restore(st)
    with pytest.raises(ValueError, match="divisible"):
        ShardedBatchLoader(ds, 8, 32, seq_shard_count=5)


def test_seq_shard_info_from_mesh():
    """seq_shard_info maps a process's devices to the seq-axis block it
    should load."""
    from tony_tpu.parallel import MeshSpec, build_mesh
    from tony_tpu.data import seq_shard_info

    mesh = build_mesh(MeshSpec(fsdp=1, seq=8))
    # single process owning everything -> load the full sequence
    assert seq_shard_info(mesh, 0) == (0, 1)
    # simulate 4 hosts of 2 devices tiling the seq axis contiguously:
    # device at seq coord c belongs to process c // 2
    coord = {id(d): i for i, d in enumerate(mesh.devices.flat)}
    dp = lambda d: coord[id(d)] // 2
    assert seq_shard_info(mesh, 0, device_process=dp) == (0, 4)
    assert seq_shard_info(mesh, 3, device_process=dp) == (3, 4)
    # interleaved layout (process owns coords {0, 4}) must be rejected
    dp_bad = lambda d: coord[id(d)] % 4
    with pytest.raises(ValueError, match="non-contiguous"):
        seq_shard_info(mesh, 0, device_process=dp_bad)
    # no seq axis -> no seq sharding
    dp_mesh = build_mesh(MeshSpec(data=2, fsdp=4))
    assert seq_shard_info(dp_mesh, 0) == (0, 1)


def test_token_file_rejects_future_version(tmp_path):
    p = write_tokens(tmp_path / "v.bin", [1, 2, 3])
    raw = bytearray(p.read_bytes())
    raw[4:8] = (99).to_bytes(4, "little")
    p.write_bytes(bytes(raw))
    with pytest.raises(ValueError, match="version"):
        TokenDataset.from_bin(p)


def test_write_tokens_rejects_negative():
    import tempfile, pathlib
    with tempfile.TemporaryDirectory() as td:
        with pytest.raises(ValueError, match="negative"):
            write_tokens(pathlib.Path(td) / "n.bin", [-1, 5])


def test_prefetch_close_with_blocked_producer_depth1():
    """depth=1 close() while the producer is blocked on a full queue must
    not leave the thread alive (regression: final _DONE put deadlocked)."""
    def forever():
        i = 0
        while True:
            yield i
            i += 1

    pre = PrefetchLoader(forever(), depth=1)
    next(pre)
    pre.close()
    assert not pre._thread.is_alive()


def test_batch_axes_follow_rules_table():
    from tony_tpu.parallel import MeshSpec, build_mesh, DP_RULES
    from tony_tpu.data import sharded_batch_axes, loader_shard_info, BATCH_AXES

    assert BATCH_AXES == tuple(DP_RULES["batch"])  # single source of truth
    mesh = build_mesh(MeshSpec(data=2, fsdp=4))
    # custom rules that consume batch over data only
    rules = {"batch": ("data",)}
    assert sharded_batch_axes(mesh, rules=rules) == ("data",)
    assert loader_shard_info(mesh, 1, 2, rules={"batch": ()}) == (0, 1)


def test_max_token_scans_whole_stream(tmp_path):
    toks = np.zeros(5000, dtype=np.int64)
    toks[4999] = 300  # id at the very end must be found
    p = write_tokens(tmp_path / "t.bin", toks)
    ds = TokenDataset.from_bin(p)
    assert ds._header_max == 300  # write_tokens caches it -> O(1) validation
    assert ds.max_token() == 300
    # files from other writers (field = 0) fall back to the full chunked scan
    raw = bytearray(p.read_bytes())
    raw[12:16] = b"\x00" * 4
    p.write_bytes(bytes(raw))
    ds2 = TokenDataset.from_bin(p)
    assert ds2._header_max is None
    assert ds2.max_token(chunk=64) == 300
    # append keeps the cached max current
    write_tokens(tmp_path / "t2.bin", [5])
    write_tokens(tmp_path / "t2.bin", [9, 2])
    assert TokenDataset.from_bin(tmp_path / "t2.bin").max_token() == 9


def test_lm_train_data_on_seq_mesh(tmp_path):
    """Regression: --data with a sequence-parallel mesh must place batches
    with the step's P(batch, seq) sharding (a batch-only spec crashed jit)."""
    import json
    from tony_tpu.examples import lm_train

    rng = np.random.default_rng(1)
    path = write_tokens(tmp_path / "c.bin", rng.integers(0, 128, size=40000))
    out = tmp_path / "m.json"
    rc = lm_train.main([
        "--steps", "2", "--batch-size", "2", "--seq-len", "64",
        "--vocab", "128", "--d-model", "32", "--n-layers", "1",
        "--n-heads", "2", "--d-ff", "64", "--dtype", "float32",
        "--mesh", "seq=8", "--data", str(path), "--metrics-out", str(out),
    ])
    assert rc == 0
    assert np.isfinite(json.loads(out.read_text())["final_loss"])


def test_restore_validates_stream_addressing_fields():
    ds = _toy_dataset()
    state = ShardedBatchLoader(ds, 8, 32, seed=5).state()
    with pytest.raises(ValueError, match="global_batch"):
        ShardedBatchLoader(ds, 4, 32, seed=5).restore(state)
    with pytest.raises(ValueError, match="seq_len"):
        ShardedBatchLoader(ds, 8, 16, seed=5).restore(state)


def test_device_put_handles_mixed_rank_leaves():
    """1-D per-example leaves (lengths/weights) must get a batch-only spec,
    not a rank-2 spec that crashes placement."""
    from tony_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=2, fsdp=4))
    batch = {"tokens": np.zeros((8, 16), np.int32),
             "weights": np.ones((8,), np.float32)}
    placed = device_put_sharded_batch(batch, mesh)
    assert placed["tokens"].shape == (8, 16)
    assert placed["weights"].shape == (8,)


def test_from_raw_headerless_stream(tmp_path):
    """nanoGPT-style raw uint16 files load via from_raw; lm_train falls back
    to it automatically when the TTPU magic is absent."""
    import json
    from tony_tpu.examples import lm_train

    toks = np.random.default_rng(3).integers(0, 200, size=30000).astype(np.uint16)
    p = tmp_path / "raw.bin"
    p.write_bytes(toks.tobytes())
    ds = TokenDataset.from_raw(p)
    assert len(ds) == 30000
    np.testing.assert_array_equal(ds.window(0, 10), toks[:10].astype(np.int32))
    assert ds.max_token() == int(toks.max())

    out = tmp_path / "m.json"
    rc = lm_train.main([
        "--steps", "2", "--batch-size", "8", "--seq-len", "32",
        "--vocab", "256", "--d-model", "32", "--n-layers", "1",
        "--n-heads", "2", "--d-ff", "64", "--dtype", "float32",
        "--mesh", "data=2,fsdp=4", "--data", str(p), "--metrics-out", str(out),
    ])
    assert rc == 0
    assert np.isfinite(json.loads(out.read_text())["final_loss"])


def test_bad_ttpu_header_not_reinterpreted_as_raw(tmp_path):
    """A TTPU file with an unsupported version must error in lm_train, not
    silently decode its header bytes as tokens via the raw fallback."""
    from tony_tpu.data.dataset import has_ttpu_magic
    from tony_tpu.examples import lm_train

    p = write_tokens(tmp_path / "v.bin", np.zeros(30000, dtype=np.int64))
    raw = bytearray(p.read_bytes())
    raw[4:8] = (99).to_bytes(4, "little")
    p.write_bytes(bytes(raw))
    assert has_ttpu_magic(p)
    with pytest.raises(ValueError, match="version"):
        lm_train.main([
            "--steps", "1", "--batch-size", "8", "--seq-len", "32",
            "--vocab", "256", "--d-model", "32", "--n-layers", "1",
            "--n-heads", "2", "--d-ff", "64", "--dtype", "float32",
            "--mesh", "data=2,fsdp=4", "--data", str(p),
        ])


def test_dataset_split_views(tmp_path):
    ds = _toy_dataset(n=1000)
    train, val = ds.split(0.1)
    assert len(train) == 900 and len(val) == 100
    np.testing.assert_array_equal(val.window(0, 5), ds.window(900, 5))
    with pytest.raises(ValueError, match="holdout_frac"):
        ds.split(1.5)


def test_lm_train_eval_split(tmp_path):
    """--eval-every reports held-out loss/ppl in metrics (train/serve loop
    parity with real frameworks)."""
    import json
    from tony_tpu.examples import lm_train

    rng = np.random.default_rng(5)
    path = write_tokens(tmp_path / "c.bin", rng.integers(0, 128, size=60000))
    out = tmp_path / "m.json"
    rc = lm_train.main([
        "--steps", "4", "--batch-size", "8", "--seq-len", "32",
        "--vocab", "128", "--d-model", "32", "--n-layers", "1",
        "--n-heads", "2", "--d-ff", "64", "--dtype", "float32",
        "--mesh", "data=2,fsdp=4", "--data", str(path),
        "--eval-every", "2", "--eval-batches", "2",
        "--metrics-out", str(out),
    ])
    assert rc == 0
    metrics = json.loads(out.read_text())
    assert np.isfinite(metrics["eval_loss"]) and metrics["eval_ppl"] > 1
