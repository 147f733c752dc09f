"""Tokenized-text file pipeline (VERDICT round-1 missing #2): DDLTOK01
format round-trip, deterministic epoch shuffling, Grain-backed variant,
training GPT-2 from an on-disk token file, and Grain checkpointable
iterator state.
"""

import subprocess
import sys

import numpy as np
import pytest

from distributeddeeplearning_tpu import data as data_lib
from distributeddeeplearning_tpu import models
from distributeddeeplearning_tpu.data_text import (
    GrainTokenFileLM,
    TokenFileLM,
    TokenFileMLM,
    grain_per_host_loader,
    read_token_file,
    write_token_file,
)
from distributeddeeplearning_tpu.train import Trainer, fit, get_task, make_optimizer


@pytest.fixture
def token_file(tmp_path):
    path = str(tmp_path / "corpus.tok")
    rng = np.random.default_rng(0)
    write_token_file(path, rng.integers(0, 250, 4097, dtype=np.int64), 256)
    return path


def test_round_trip_and_header(tmp_path):
    path = str(tmp_path / "t.tok")
    tokens = np.arange(1000) % 50257
    write_token_file(path, tokens, 50257)
    back, vocab = read_token_file(path)
    assert vocab == 50257 and back.dtype == np.uint16
    np.testing.assert_array_equal(back, tokens)
    # Large vocab gets uint32.
    write_token_file(path, [70000], 70001)
    back, vocab = read_token_file(path)
    assert back.dtype == np.uint32 and back[0] == 70000
    # Bad files fail loudly.
    (tmp_path / "junk").write_bytes(b"not a token file, definitely not one")
    with pytest.raises(ValueError, match="DDLTOK01"):
        read_token_file(str(tmp_path / "junk"))
    (tmp_path / "short").write_bytes(b"tiny")
    with pytest.raises(ValueError, match="truncated"):
        read_token_file(str(tmp_path / "short"))
    with pytest.raises(ValueError, match="out of range"):
        write_token_file(path, [5], 3)


def test_lm_batches_deterministic_and_cover_epoch(token_file):
    ds = TokenFileLM(path=token_file, batch_size=8, seq_len=32, seed=1)
    # 4097 tokens -> 128 sequences of 32 (+1 lookahead) -> 16 batches/epoch.
    assert ds._batches_per_epoch == 16
    b0 = ds.batch(0)
    assert b0["tokens"].shape == (8, 33) and b0["tokens"].dtype == np.int32
    np.testing.assert_array_equal(b0["tokens"], ds.batch(0)["tokens"])
    # The lookahead token of each row is the first token of the next slice:
    # row j covers tokens[j*32 : j*32+33], so content must match the mmap.
    raw, _ = read_token_file(token_file)
    ds_noshuffle_row = ds._perm(0)[0] * 32
    np.testing.assert_array_equal(
        b0["tokens"][0], np.asarray(raw[ds_noshuffle_row : ds_noshuffle_row + 33])
    )
    # Every sequence appears exactly once per epoch; epochs differ.
    rows_e0 = np.concatenate(
        [ds.batch(i)["tokens"][:, 0] for i in range(16)]
    )
    rows_e1 = np.concatenate(
        [ds.batch(16 + i)["tokens"][:, 0] for i in range(16)]
    )
    assert rows_e0.shape == (128,)
    assert not np.array_equal(rows_e0, rows_e1)
    assert sorted(ds._perm(0)) == list(range(128))


def test_mlm_batches(token_file):
    ds = TokenFileMLM(
        path=token_file, batch_size=8, seq_len=32, mask_token_id=255, seed=2
    )
    b = ds.batch(0)
    assert b["input_tokens"].shape == (8, 32) and b["labels"].shape == (8, 32)
    masked = b["labels"] >= 0
    assert 0.03 < masked.mean() < 0.4  # ~15% of positions
    assert (b["input_tokens"][masked] == 255).all()
    unmasked_equal = b["input_tokens"][~masked] == b["labels"][~masked]
    assert not unmasked_equal.any()  # unmasked labels are -1 (ignored)
    np.testing.assert_array_equal(b["labels"], ds.batch(0)["labels"])


def test_grain_variant_deterministic_and_covers(token_file):
    ds = GrainTokenFileLM(path=token_file, batch_size=8, seq_len=32, seed=3)
    b0 = ds.batch(0)
    assert b0["tokens"].shape == (8, 33) and b0["tokens"].dtype == np.int32
    np.testing.assert_array_equal(b0["tokens"], ds.batch(0)["tokens"])
    ds2 = GrainTokenFileLM(path=token_file, batch_size=8, seq_len=32, seed=3)
    np.testing.assert_array_equal(ds2.batch(5)["tokens"], ds.batch(5)["tokens"])
    # A full epoch (16 batches) visits all 128 sequences once.
    firsts = np.concatenate([ds.batch(i)["tokens"][:, 0] for i in range(16)])
    raw, _ = read_token_file(token_file)
    expected = np.sort(np.asarray(raw[: 128 * 32 : 32]))
    np.testing.assert_array_equal(np.sort(firsts), expected)


def test_registered_kinds(token_file):
    for kind in ("token_file_lm", "token_file_mlm", "grain_token_file_lm"):
        ds = data_lib.make_dataset(
            kind, path=token_file, batch_size=4, seq_len=16
        )
        assert ds.batch(0)


def test_eval_split_for_file_kinds(token_file, tmp_path):
    from distributeddeeplearning_tpu.config import DataConfig

    # eval_path selects a held-out file.
    heldout = str(tmp_path / "val.tok")
    write_token_file(heldout, np.zeros(2049, np.int64), 256)
    cfg = DataConfig(
        kind="token_file_lm", batch_size=4, seq_len=32,
        path=token_file, eval_path=heldout,
    )
    assert cfg.dataset_kwargs()["path"] == token_file
    assert cfg.eval_dataset_kwargs()["path"] == heldout
    # A bare eval_seed on a file kind would just reshuffle the training
    # file and report it as eval — rejected loudly.
    bad = DataConfig(
        kind="token_file_lm", batch_size=4, seq_len=32,
        path=token_file, eval_seed=7,
    )
    with pytest.raises(ValueError, match="eval_path"):
        bad.eval_dataset_kwargs()


def test_eval_without_heldout_file_warns_loudly(token_file, capsys):
    from distributeddeeplearning_tpu.config import DataConfig

    # Neither eval_path nor eval_seed on a file kind: eval falls back to
    # the training file, which must be announced, not silent (it makes
    # every eval_* metric a training-loss number in disguise).
    cfg = DataConfig(
        kind="token_file_lm", batch_size=4, seq_len=32, path=token_file,
    )
    kwargs = cfg.eval_dataset_kwargs()
    assert kwargs["path"] == token_file
    err = capsys.readouterr().err
    assert "TRAINING file" in err and "eval_path" in err


def test_gpt2_trains_from_token_file(token_file, mesh8):
    ds = TokenFileLM(path=token_file, batch_size=16, seq_len=32, seed=0)
    model = models.get_model("gpt2", size="tiny", vocab_size=256, max_len=64)
    trainer = Trainer(
        model, make_optimizer("adamw", 1e-3), get_task("lm"), mesh8,
        donate=False,
    )
    state = trainer.init(0, ds.batch(0))
    batches = data_lib.sharded_batches(ds.iter_from(0), mesh8)
    state, hist = fit(trainer, state, batches, steps=8, log_every=4)
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_prepare_data_cli_byte_tokenizer(tmp_path):
    src = tmp_path / "corpus.txt"
    src.write_text("hello tokenized world " * 400)
    out = tmp_path / "corpus.tok"
    res = subprocess.run(
        [
            sys.executable, "-m", "distributeddeeplearning_tpu.prepare_data",
            "--input", str(src), "--output", str(out), "--tokenizer", "byte",
        ],
        capture_output=True, text=True,
    )
    assert res.returncode == 0, res.stderr
    tokens, vocab = read_token_file(str(out))
    assert vocab == 256
    assert bytes(np.asarray(tokens[:5], np.uint8)) == b"hello"


def test_grain_per_host_loader_state_roundtrip(token_file):
    loader = grain_per_host_loader(token_file, batch_size=4, seq_len=32, seed=1)
    it = iter(loader)
    first_three = [next(it) for _ in range(3)]
    saved = it.get_state()
    fourth = next(it)
    # Restore: a fresh iterator resumes exactly at batch 4.
    it2 = iter(loader)
    it2.set_state(saved)
    np.testing.assert_array_equal(next(it2), fourth)
    assert first_three[0].shape == (4, 33)


def test_grain_per_host_loader_with_worker_processes(token_file):
    # num_workers>0 pickles the source into each worker process; the source
    # must ship its PATH and re-open the memmap per process (shipping the
    # memmap itself would materialize the whole corpus in every worker's
    # RAM). Grain's batch order differs BETWEEN worker counts, so the
    # contract is: workers run at all (the pickling path), and the stream is
    # deterministic at a fixed worker count.
    a = iter(grain_per_host_loader(token_file, batch_size=4, seq_len=32,
                                   seed=1, num_workers=2))
    b = iter(grain_per_host_loader(token_file, batch_size=4, seq_len=32,
                                   seed=1, num_workers=2))
    for _ in range(3):
        xa, xb = np.asarray(next(a)), np.asarray(next(b))
        assert xa.shape == (4, 33)
        np.testing.assert_array_equal(xa, xb)


def test_grain_source_pickles_without_tokens():
    import pickle

    from distributeddeeplearning_tpu.data_text import _GrainSeqSource

    src = _GrainSeqSource("/nonexistent/x.tok", 32, 7)
    blob = pickle.dumps(src)
    clone = pickle.loads(blob)
    assert clone._path == "/nonexistent/x.tok"
    assert clone._tokens is None  # memmap never travels through the pickle
    assert len(clone) == 7
