"""Trainer / data / metrics tests (reference analogues: trainer loops in
trainer.py + GPT2_Trainer.py, dataset plumbing utils/Dataloader.py,
metrics utils/metrics.py)."""

import numpy as np
import pytest

import jax

from quintnet_tpu.core.config import Config
from quintnet_tpu.data import (
    ArrayDataset,
    ByteTokenizer,
    SummarizationDataset,
    load_mnist,
    make_batches,
)
from quintnet_tpu.models.vit import ViTConfig, vit_model_spec
from quintnet_tpu.train import metrics as M
from quintnet_tpu.train.trainer import Trainer

CFG = ViTConfig(image_size=28, patch_size=7, in_channels=1, hidden_dim=16,
                depth=4, num_heads=2, num_classes=10)


def test_synthetic_mnist_learnable_and_split_consistent():
    xtr, ytr = load_mnist(split="train", synthetic_size=2048)
    xte, yte = load_mnist(split="test", synthetic_size=512)
    assert xtr.shape == (2048, 28, 28, 1) and ytr.shape == (2048,)
    # same class prototypes across splits: same-class means correlate.
    # The task is deliberately low-SNR (Bayes acc ~94%, see
    # synthetic_mnist docstring) so the correlation needs enough samples
    # per class to emerge from the noise.
    m_tr = xtr[ytr == 3].mean(0).ravel()
    m_te = xte[yte == 3].mean(0).ravel()
    corr = np.corrcoef(m_tr, m_te)[0, 1]
    assert corr > 0.35, corr


def test_make_batches_shapes():
    ds = ArrayDataset(np.zeros((10, 2)), np.arange(10))
    bs = list(make_batches(ds, 4, shuffle=False))
    assert len(bs) == 2 and bs[0][0].shape == (4, 2)


def test_summarization_encoding_masks_prompt():
    tok = ByteTokenizer()
    ds = SummarizationDataset([("hello world", "hi")], tok, max_length=32)
    ids, labels = ds.encode_row("hello world", "hi")
    assert ids.shape == (32,) and labels.shape == (32,)
    n_prompt = len(tok.encode("hello world" + ds.PROMPT))
    assert (labels[:n_prompt] == -100).all()
    assert (labels[n_prompt:n_prompt + 2] == ids[n_prompt:n_prompt + 2]).all()
    assert (labels[n_prompt + 2:] == -100).all()  # padding masked


def test_summarization_encoding_keeps_summary_on_overflow():
    """Long articles must left-truncate so the summary labels survive
    (right-truncation silently masks every label -> zero loss)."""
    tok = ByteTokenizer()
    long_article = "x" * 100
    ds = SummarizationDataset([(long_article, "hi")], tok, max_length=32)
    ids, labels = ds.encode_row(long_article, "hi")
    assert ids.shape == (32,)
    n_valid = int((labels != -100).sum())
    assert n_valid == len(tok.encode("hi"))
    # the TL;DR marker at the prompt tail survives the left-truncation
    marker = tok.encode(ds.PROMPT)
    assert list(ids[32 - n_valid - len(marker):32 - n_valid]) == list(marker)


def test_rouge_bleu():
    r = M.rouge_scores("the cat sat", "the cat sat")
    assert r["rouge1"] == r["rouge2"] == r["rougeL"] == 1.0
    r2 = M.rouge_scores("the cat", "the dog")
    assert 0 < r2["rouge1"] < 1 and r2["rouge2"] == 0.0
    assert M.bleu_score("the cat sat on the mat", ["the cat sat on the mat"]) \
        == pytest.approx(1.0)
    agg = M.compute_rouge_bleu(["a b c"], ["a b d"])
    assert set(agg) == {"rouge1", "rouge2", "rougeL", "bleu"}


def test_trainer_fit_reduces_loss_dp():
    cfg = Config.from_dict({
        "mesh_dim": [4], "mesh_name": ["dp"],
        "training": {"batch_size": 32, "epochs": 3, "learning_rate": 1e-3,
                     "optimizer": "adam", "log_every": 0},
    })
    model = vit_model_spec(CFG)
    x, y = load_mnist(split="train", synthetic_size=128)
    ds = ArrayDataset(x, y)
    trainer = Trainer(cfg, model, task_type="classification",
                      log_fn=lambda s: None)
    hist = trainer.fit(
        lambda ep: make_batches(ds, 32, seed=ep),
        val_batches_fn=lambda ep: make_batches(ds, 32, shuffle=False),
    )
    assert hist.train_loss[-1] < hist.train_loss[0]
    assert len(hist.val_loss) == 3


def test_trainer_resume(tmp_path):
    cfg = Config.from_dict({
        "mesh_dim": [2], "mesh_name": ["dp"],
        "training": {"batch_size": 16, "epochs": 2, "optimizer": "adam",
                     "log_every": 0},
    })
    model = vit_model_spec(CFG)
    x, y = load_mnist(split="train", synthetic_size=64)
    ds = ArrayDataset(x, y)
    ck = str(tmp_path / "ck")

    t1 = Trainer(cfg, model, task_type="classification", checkpoint_dir=ck,
                 log_fn=lambda s: None)
    t1.fit(lambda ep: make_batches(ds, 16, seed=ep), epochs=1)

    t2 = Trainer(cfg, model, task_type="classification", checkpoint_dir=ck,
                 log_fn=lambda s: None)
    params, opt_state, start = t2.resume_or_init()
    assert start == 1  # resumes after epoch 0


def test_history_to_jsonl(tmp_path):
    import json

    from quintnet_tpu.train.trainer import History

    h = History(train_loss=[2.0, 1.5], val_loss=[1.8],
                val_metric=[0.5], wall_time_s=3.2,
                best_val_loss=1.8, best_epoch=0)
    p = str(tmp_path / "hist.jsonl")
    h.to_jsonl(p)
    rows = [json.loads(l) for l in open(p)]
    assert rows[0] == {"epoch": 0, "train_loss": 2.0, "val_loss": 1.8,
                       "val_metric": 0.5}
    assert rows[1] == {"epoch": 1, "train_loss": 1.5}
    assert rows[-1]["best_epoch"] == 0 and rows[-1]["wall_time_s"] == 3.2


def test_parity_report_flags_stale_legs(tmp_path, monkeypatch):
    import json

    from quintnet_tpu.tools import parity_run

    art = tmp_path / "parity"
    art.mkdir()
    base = {"epochs": 1, "train_loss": [1.0], "val_accuracy": [0.5],
            "val_perplexity": [3.0], "wall_time_s": 1.0}
    for task in ("vit", "gpt2"):
        mkey = "val_accuracy" if task == "vit" else "val_perplexity"
        single = {**base, "task": task, "mode": "single", "data_fp": "aaa"}
        three = {**base, "task": task, "mode": "3d",
                 "data_fp": "aaa" if task == "gpt2" else "bbb"}
        for r in (single, three):
            (art / f"{task}_{r['mode']}.json").write_text(json.dumps(r))
    monkeypatch.setattr(parity_run, "ART_DIR", str(art))
    md = parity_run.report()
    assert "INCOMPARABLE" in md           # vit legs differ -> flagged
    assert "GPT2 (1 epochs)" in md        # gpt2 legs match -> compared
    assert md.count("PASS") == 1


def test_compilation_cache_helper(tmp_path, monkeypatch):
    """The helper takes no directory: the cache is placed from outside
    (JAX_COMPILATION_CACHE_DIR, which JAX reads itself at import — here
    the config is pointed at the same place by hand, as a process
    started under the variable would find it) and the helper only
    lowers the two thresholds so even a tiny program is cached."""
    import os

    from quintnet_tpu.core import runtime

    d = str(tmp_path / "xla")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    jax.config.update("jax_compilation_cache_dir", d)
    assert runtime.enable_compilation_cache() == d
    assert jax.config.jax_compilation_cache_dir == d

    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return jnp.cos(x) @ x.T

    f(jnp.ones((128, 128))).block_until_ready()
    assert sum(len(fs) for _, _, fs in os.walk(d)) > 0
    # restore defaults for the rest of the session
    jax.config.update("jax_compilation_cache_dir", None)
