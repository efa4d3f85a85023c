"""train/tasks.py: a task is one object handed to the loop.

The three tasks' literals are pinned; the seam's witness is a fourth task
the repo does not have, built here and trained through ``Trainer.fit`` with
the trainer unedited.
"""

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from distributedpytorch_tpu.train import Config, apply_overrides, tasks


@pytest.mark.parametrize("name,loss_type,device_keys,init_input,best", [
    ("instance", "multi_sigmoid", ("concat", "crop_gt", "crop_void"),
     {"input_shape": (1, 48, 64, 4)}, ("jaccard", 0.25)),
    ("semantic", "multi_softmax", ("concat", "crop_gt", "crop_void"),
     {"input_shape": (1, 48, 64, 4)}, ("jaccard", 0.25)),
    ("tokens", "next_token", ("tokens",),
     {"input_shape": (1, 24), "input_dtype": jnp.int32},
     ("neg_loss", -2.0)),
])
def test_a_task_states_what_the_loop_asked_by_name(
        name, loss_type, device_keys, init_input, best):
    cfg = apply_overrides(Config(), ["data.crop_size=[48,64]",
                                     "data.seq_len=24"])
    task = tasks.get(name)
    assert task is tasks.TASKS[name] and task.name == name
    assert task.loss_type == loss_type
    assert task.device_keys == device_keys
    assert task.init_input(cfg) == init_input
    assert task.best({"loss": 2.0, "jaccard": 0.25, "miou": 0.25}) == best
    # the image tasks start from checkpoint.best_metric_init; the negated
    # loss never reaches the Jaccard scale's 0
    assert task.best_init == (-1e30 if name == "tokens" else None)
    # a token batch has no device stage, so the governor has no flip
    assert (task.device_stage is None) == (name == "tokens")
    assert task.pack_kind == (None if name == "tokens" else name)
    assert task.pack_area_thres(cfg) == (
        cfg.data.area_thres if name == "instance" else None)


def test_an_unknown_task_is_named_with_the_known_ones():
    with pytest.raises(ValueError, match=r"unknown task: 'nope' "
                       r"\(instance \| semantic \| tokens\)"):
        tasks.get("nope")


@pytest.mark.parametrize("name,override,match", [
    ("instance", "model.nclass=21", "requires model.nclass=1"),
    ("instance", "eval_full_res=true", "semantic task only"),
    ("instance", "data.packbits_masks=true", "packs the BINARY"),
    ("semantic", "data.packbits_masks=true", "packs the BINARY"),
    ("semantic", "data.device_guidance=true", "instance task only"),
    ("tokens", "eval_tta_flip=true", "semantic task only"),
    ("tokens", "data.device_guidance=true", "instance task only"),
])
def test_a_task_checks_its_own_options(name, override, match):
    tasks.get(name).check(Config())
    with pytest.raises(ValueError, match=match):
        tasks.get(name).check(apply_overrides(Config(), [override]))


class _Ramps:
    """The witness's own source: sequences that count upward from a seeded
    start, modulo the vocabulary — learnable, unlike uniform ids."""

    def __init__(self, n: int, seq_len: int, vocab: int, seed: int):
        self.n, self.seq_len, self.vocab, self.seed = n, seq_len, vocab, seed

    def __len__(self) -> int:
        return self.n

    def __getitem__(self, index: int, rng=None) -> dict:
        start = np.random.default_rng((self.seed, int(index))).integers(
            self.vocab)
        return {"tokens": ((start + np.arange(self.seq_len)) % self.vocab
                           ).astype(np.int32)}

    def __str__(self) -> str:
        return f"Ramps(n={self.n})"


def test_a_task_the_repo_does_not_have_trains_through_the_loop(
        tmp_path, monkeypatch):
    """The seam's witness: a fourth task — the ``tiny`` token model on its
    own source, gated on its own metric — put into the registry by this
    test alone, trains two steps and validates once through
    ``Trainer.fit``.  What the next ``model_config`` PR needs to be true."""
    from distributedpytorch_tpu import models
    from distributedpytorch_tpu.train import Trainer

    def datasets(cfg, ctx):
        vocab = models.build_from_config(
            cfg.model, dtype=cfg.model.dtype).vocab_size
        return (_Ramps(cfg.data.token_samples, cfg.data.seq_len, vocab,
                       cfg.seed),
                _Ramps(cfg.data.token_val_samples, cfg.data.seq_len, vocab,
                       cfg.seed + 1),
                tasks.ValWire())

    def evaluate(*args):
        metrics = tasks.TOKENS.evaluate(*args)
        return dict(metrics, neg_perplexity=-metrics["perplexity"])

    ramps = dataclasses.replace(
        tasks.TOKENS, name="ramps", datasets=datasets, evaluate=evaluate,
        best=lambda m: ("neg_perplexity", m["neg_perplexity"]))
    monkeypatch.setitem(tasks.TASKS, "ramps", ramps)
    monkeypatch.setitem(models.MODEL_TASKS, "nemotron_h",
                        ("tokens", "ramps"))

    cfg = apply_overrides(Config(), [
        "task=ramps", "model.name=nemotron_h", "data.train_batch=8",
        "data.val_batch=8", "data.seq_len=24", "data.token_samples=16",
        "data.token_val_samples=8", "optim.lr=1e-2", "epochs=1",
        "checkpoint.async_save=false", "log_every_steps=1"])
    tr = Trainer(dataclasses.replace(cfg, work_dir=str(tmp_path / "runs")))
    try:
        assert tr.task is ramps and str(tr.train_set) == "Ramps(n=16)"
        # a token batch has no host augmentation: the governor's flip
        # is refused with a reason, not attempted
        assert tr._feed_flip_available() == (
            False, "task=ramps has no host augmentation to move")
        hist = tr.fit()
        best = tr.ckpt.best_metric
    finally:
        tr.close()
    assert int(tr.state.step) == 2 and np.isfinite(hist["train_loss"][0])
    val = hist["val"][0]
    assert val["neg_perplexity"] == -val["perplexity"] < -1.0
    assert best == pytest.approx(val["neg_perplexity"])
    with open(os.path.join(tr.run_dir, "metrics.jsonl")) as f:
        flat = {k: v for ln in f for k, v in json.loads(ln).items()}
    assert flat["val/new_best_neg_perplexity"] == pytest.approx(best)
    assert "val/new_best_neg_loss" not in flat
