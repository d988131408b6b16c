"""Native C++ data loader tests: file-format roundtrip, epoch coverage /
DP-partition correctness, determinism, resume-skip, and bit-identical parity
between the native and numpy paths (same splitmix64 Fisher-Yates)."""

import os

import numpy as np
import pytest

from neuronx_distributed_tpu.data import (
    TokenDataLoader,
    TokenDataset,
    read_token_file,
    write_token_file,
)
from neuronx_distributed_tpu.data.loader import _load_native, _shuffled_chunks


@pytest.fixture
def token_file(tmp_path):
    toks = np.arange(1, 4097, dtype=np.int32) % 50000
    path = str(tmp_path / "corpus.nxdt")
    write_token_file(path, toks)
    return path, toks


def test_token_file_roundtrip(token_file):
    path, toks = token_file
    back = read_token_file(path)
    np.testing.assert_array_equal(back.astype(np.int64), toks.astype(np.int64))


def test_native_library_builds():
    """The C++ loader must compile on this toolchain (g++ is baked in); the
    numpy fallback is for g++-less environments only."""
    assert _load_native() is not None


def test_native_library_is_named_by_source_content(tmp_path, monkeypatch):
    """Staleness is decided by content, not mtime: a copied tree may bring a
    ``_build/*.so`` of another ``loader.cpp`` along, newer than the source —
    it must simply not be the file this source loads."""
    from neuronx_distributed_tpu.data import loader

    assert loader.loader_backend() == "native"
    current = loader._lib_path()
    assert os.path.exists(current)
    edited = tmp_path / "loader.cpp"
    with open(loader._CSRC, "rb") as f:
        edited.write_bytes(f.read() + b"\n// a later edit\n")
    os.utime(edited, (0, 0))  # older than any build: mtime must not matter
    monkeypatch.setattr(loader, "_CSRC", str(edited))
    assert loader._lib_path() != current


def _collect(loader):
    return list(loader)


def test_epoch_covers_every_chunk_once(token_file):
    path, toks = token_file
    ds = TokenDataset(path)
    seq = 64
    total = ds.num_chunks(seq)
    seen = []
    for rank in range(4):
        dl = TokenDataLoader(ds, batch_size=2, seq_len=seq, dp_rank=rank,
                             dp_size=4, seed=7)
        for b in dl:
            assert b["ids"].shape == (2, seq) and b["labels"].shape == (2, seq)
            # label shift invariant
            np.testing.assert_array_equal(b["ids"][:, 1:], b["labels"][:, :-1])
            seen.extend(b["ids"][:, 0].tolist())
        dl.close()
    # chunk i starts at token i*seq -> starting tokens identify chunks; all
    # distinct means no chunk was served twice across ranks
    assert len(seen) == len(set(seen))
    assert len(seen) >= (total // 2 // 4) * 2 * 4 - 8  # whole-batch truncation only
    ds.close()


def test_uniform_batch_count_across_ranks(token_file):
    """Every dp rank must see the same number of batches even when the chunk
    count does not divide dp_size (63 chunks / dp=4 here) — otherwise the
    longer ranks block in the first collective after a short rank's loader
    is exhausted.  Both the native and numpy paths must agree."""
    path, _ = token_file
    ds = TokenDataset(path)
    seq = 64
    total = ds.num_chunks(seq)
    assert total % 4 != 0  # the fixture must exercise the ragged case
    counts, yielded = [], []
    for rank in range(4):
        dl = TokenDataLoader(ds, batch_size=2, seq_len=seq, dp_rank=rank,
                             dp_size=4, seed=7)
        counts.append(len(dl))
        yielded.append(sum(1 for _ in dl))
        dl.close()
    assert counts == yielded
    assert len(set(counts)) == 1, counts
    assert counts[0] == (total // 4) // 2
    ds.close()


def test_determinism_and_epoch_variation(token_file):
    path, _ = token_file
    ds = TokenDataset(path)

    def run(epoch):
        dl = TokenDataLoader(ds, batch_size=2, seq_len=32, seed=123)
        dl.set_epoch(epoch)
        out = np.concatenate([b["ids"] for b in dl])
        dl.close()
        return out

    a, b = run(0), run(0)
    np.testing.assert_array_equal(a, b)
    c = run(1)
    assert not np.array_equal(a, c)
    ds.close()


def test_native_matches_numpy_fallback(token_file):
    path, toks = token_file
    ds = TokenDataset(path)
    assert ds.is_native
    dl = TokenDataLoader(ds, batch_size=2, seq_len=32, dp_rank=1, dp_size=2, seed=5)
    dl.set_epoch(3)
    native = np.concatenate([b["ids"] for b in dl])
    dl.close()
    ds.close()

    # numpy fallback reconstruction from the shared shuffle
    total = (toks.size - 1) // 32
    order = _shuffled_chunks(total, seed=5, epoch=3)
    mine = order[1::2]
    mine = mine[: (len(mine) // 2) * 2]
    want = np.stack([toks[int(c) * 32:int(c) * 32 + 32] for c in mine]).astype(np.int32)
    np.testing.assert_array_equal(native, want.reshape(native.shape))


def test_skip_resume(token_file):
    path, _ = token_file
    ds = TokenDataset(path)
    dl = TokenDataLoader(ds, batch_size=2, seq_len=32, seed=9)
    dl.set_epoch(0)
    full = [b["ids"] for b in dl]
    dl.set_epoch(0, skip_batches=3)
    resumed = [b["ids"] for b in dl]
    assert len(resumed) == len(full) - 3
    for a, b in zip(full[3:], resumed):
        np.testing.assert_array_equal(a, b)
    dl.close()
    ds.close()


def test_uint16_storage(tmp_path):
    toks = np.arange(2000, dtype=np.uint16)
    path = str(tmp_path / "small.nxdt")
    write_token_file(path, toks)
    ds = TokenDataset(path)
    dl = TokenDataLoader(ds, batch_size=1, seq_len=100, seed=0)
    batch = next(iter(dl))
    assert batch["ids"].dtype == np.int32
    dl.close()
    ds.close()


def test_bad_file_rejected(tmp_path):
    path = str(tmp_path / "junk.nxdt")
    with open(path, "wb") as f:
        f.write(b"garbage-not-a-token-file-0123456789")
    with pytest.raises(ValueError):
        TokenDataset(path)


def test_exhausted_until_set_epoch(token_file):
    """Both paths are single-shot per set_epoch (identical semantics)."""
    path, _ = token_file
    ds = TokenDataset(path)
    dl = TokenDataLoader(ds, batch_size=2, seq_len=32, seed=9)
    dl.set_epoch(0)
    assert len(list(dl)) == dl.num_batches
    assert list(dl) == []  # exhausted
    dl.set_epoch(1)
    assert len(list(dl)) == dl.num_batches
    dl.close()
    ds.close()


def test_negative_tokens_rejected(tmp_path):
    with pytest.raises(ValueError, match="non-negative"):
        write_token_file(str(tmp_path / "bad.nxdt"), np.array([5, -1, 7]))


def test_concat_and_chunk():
    from neuronx_distributed_tpu.data.packing import concat_and_chunk

    docs = [np.arange(1, 6), np.arange(10, 13)]  # 5 + eos + 3 + eos = 10 tokens
    ids, labels = concat_and_chunk(docs, seq_len=4, eos_id=99)
    assert ids.shape == labels.shape == (2, 4)
    np.testing.assert_array_equal(ids[0], [1, 2, 3, 4])
    np.testing.assert_array_equal(labels[0], [2, 3, 4, 5])  # next-token shift
    np.testing.assert_array_equal(ids[1], [5, 99, 10, 11])
    np.testing.assert_array_equal(labels[1], [99, 10, 11, 12])


def test_native_pack_assign_matches_python():
    """The native first-fit placement (csrc nxd_pack_assign) must be
    bit-identical to the Python loop across ragged workloads, including
    window-eviction behavior."""
    from neuronx_distributed_tpu.data.loader import native_pack_assign
    from neuronx_distributed_tpu.data.packing import _assign_rows_py

    rng = np.random.RandomState(0)
    for trial, (n, seq_len, window) in enumerate(
            [(500, 128, 64), (2000, 64, 8), (100, 32, 0), (1, 16, 64)]):
        lengths = rng.randint(1, seq_len + 1, size=n).astype(np.int32)
        got = native_pack_assign(lengths, seq_len, window)
        assert got is not None, "native library unavailable"
        rows_n, count_n = got
        rows_p, count_p = _assign_rows_py(lengths, seq_len, window)
        assert count_n == count_p, trial
        np.testing.assert_array_equal(rows_n, rows_p, err_msg=str(trial))
    # invalid length (piece longer than seq_len) raises — never conflated
    # with native-unavailable (which would silently run the fallback)
    import pytest

    with pytest.raises(ValueError, match="length <= seq_len"):
        native_pack_assign(np.asarray([40], np.int32), 32, 64)


def test_pack_documents_first_fit():
    from neuronx_distributed_tpu.data.packing import IGNORE, pack_documents

    docs = [np.array([1, 2, 3]), np.array([4, 5]), np.array([6])]
    ids, labels, segs = pack_documents(docs, seq_len=8, eos_id=99, pad_id=0)
    # needs (3+1)+(2+1)+(1+1) = 9 slots > 8: docs 1+2 share row 0, doc 3
    # spills whole into row 1 (rows never split a short document)
    assert ids.shape == (2, 8)
    np.testing.assert_array_equal(ids[0], [1, 2, 3, 99, 4, 5, 99, 0])
    np.testing.assert_array_equal(ids[1][:2], [6, 99])
    # next-token labels; the EOS position itself predicts nothing
    np.testing.assert_array_equal(labels[0][:4], [2, 3, 99, IGNORE])
    np.testing.assert_array_equal(segs[0], [1, 1, 1, 1, 2, 2, 2, 0])
    np.testing.assert_array_equal(segs[1][:2], [1, 1])  # per-row numbering


def test_pack_documents_long_doc_split_and_pad():
    from neuronx_distributed_tpu.data.packing import IGNORE, pack_documents

    ids, labels, segs = pack_documents([np.arange(1, 12)], seq_len=6, eos_id=99)
    # 11 tokens + final EOS = 12 -> exactly two seq_len pieces, NO fake EOS
    # at the split: the boundary position's label is the doc's true next token
    assert ids.shape[0] == 2
    np.testing.assert_array_equal(ids[0], [1, 2, 3, 4, 5, 6])
    np.testing.assert_array_equal(labels[0], [2, 3, 4, 5, 6, 7])  # crosses split
    np.testing.assert_array_equal(ids[1], [7, 8, 9, 10, 11, 99])
    np.testing.assert_array_equal(labels[1], [8, 9, 10, 11, 99, IGNORE])
    assert (labels[segs == 0] == IGNORE).all()  # padding never contributes loss


def test_pack_documents_mask_separators():
    from neuronx_distributed_tpu.data.packing import IGNORE, pack_documents

    ids, labels, segs = pack_documents(
        [np.array([1, 2, 3])], seq_len=8, eos_id=99, mask_separators=True)
    # position predicting EOS is masked; the EOS position always is
    np.testing.assert_array_equal(labels[0][:4], [2, 3, IGNORE, IGNORE])


def test_build_nxdt_cli_roundtrip(tmp_path):
    """tools/build_nxdt.py: text -> NXDT -> TokenDataset -> loader batches."""
    import json
    import subprocess
    import sys

    src = tmp_path / "corpus.txt"
    src.write_text("hello world\nthe quick brown fox\n" * 20, encoding="utf-8")
    out = tmp_path / "corpus.nxdt"
    proc = subprocess.run(
        [sys.executable, "tools/build_nxdt.py", str(src), "--out", str(out),
         "--tokenizer", "bytes"],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr
    meta = json.loads(proc.stdout.strip().splitlines()[-1])
    assert meta["documents"] == 40 and meta["eos_id"] == 256

    from neuronx_distributed_tpu.data import TokenDataLoader, TokenDataset
    from neuronx_distributed_tpu.data.loader import read_token_file

    toks = read_token_file(str(out))
    assert toks.size == meta["tokens"]
    assert int(toks.max()) == 256  # eos
    ds = TokenDataset(str(out))
    loader = TokenDataLoader(ds, batch_size=2, seq_len=16, seed=0)
    loader.set_epoch(0)
    b = next(iter(loader))
    assert b["ids"].shape == (2, 16) and b["labels"].shape == (2, 16)
    ds.close()


def test_build_nxdt_jsonl(tmp_path):
    import json
    import subprocess
    import sys

    src = tmp_path / "docs.jsonl"
    src.write_text("\n".join(json.dumps({"text": f"doc {i}"}) for i in range(5)),
                   encoding="utf-8")
    out = tmp_path / "docs.nxdt"
    proc = subprocess.run(
        [sys.executable, "tools/build_nxdt.py", str(src), "--out", str(out),
         "--tokenizer", "bytes"],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout.strip().splitlines()[-1])["documents"] == 5


def test_max_token_id(tmp_path):
    path = str(tmp_path / "t.nxdt")
    write_token_file(path, np.asarray([3, 7, 255, 2], np.int64))
    ds = TokenDataset(path)
    assert ds.max_token_id() == 255
    assert ds.max_token_id() == 255  # cached path
    ds.close()
