"""The sequence model through the normal path: `Trainer` on the tiny preset
(train/trainer.py, data/tokens.py), CPU. The same trainer, loader, optimizer,
strict session and tracer as a detector's; eval, predict and serve refuse."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from replication_faster_rcnn_tpu.config import DataConfig, get_config
from replication_faster_rcnn_tpu.data.tokens import END_OF_DOCUMENT, TokenDataset

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _trainer(tmp, name, telemetry=False):
    import dataclasses

    from replication_faster_rcnn_tpu.train.trainer import Trainer

    cfg = get_config("trinity_tiny")
    cfg = cfg.replace(debug=dataclasses.replace(cfg.debug, strict=True))
    return Trainer(
        cfg, workdir=str(tmp / name), devices=jax.devices()[:1],
        telemetry_dir=str(tmp / (name + "_tel")) if telemetry else None,
    )


def _bits(tree):
    return [np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(jax.device_get(tree))]


def test_trainer_trains_strictly_saves_and_resumes_to_the_bit(tmp_path):
    """Five steps on one repeated batch under the strict session (an implicit
    transfer or a recompilation after warm-up raises): the loss falls, nothing
    is dropped, the counters reach the trace; a save restored into a second
    trainer gives the same state and the same sixth step, bit for bit."""
    first = _trainer(tmp_path, "a", telemetry=True)
    batch = next(iter(first.loader))
    assert set(batch) == {"tokens"} and batch["tokens"].shape == (2, 64) and batch["tokens"].dtype == np.int32
    staged = first._stage_batch(batch, wait=True)
    with first.strict_session():
        rows = jax.device_get([first.train_one_batch(staged=staged) for _ in range(5)])
    losses = [float(r["loss"]) for r in rows]
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    assert all(float(r["tokens_dropped"]) == 0 and float(r["skipped"]) == 0 for r in rows)
    assert 0 < float(rows[0]["router_bias_absmax"]) <= 2 * first.config.lm.load_balance_coeff
    report = first.strict.report()["programs"]["train_step"]
    assert report["dispatches"] == 5 and report["recompiles_after_warmup"] == 0
    assert first.save(kind="final")
    first._counters_pending.append((5, {k: rows[-1][k] for k in first._counters if k in rows[-1]}))
    first.flush_telemetry()
    with open(tmp_path / "a_tel" / "trace.json") as f:
        counters = {e["name"] for e in json.load(f)["traceEvents"] if e.get("ph") == "C"}
    assert {"lm/expert_assignments", "lm/expert_load_max_over_mean", "lm/tokens_dropped", "lm/router_bias_absmax"} <= counters

    second = _trainer(tmp_path, "a")
    assert second.restore() == 5
    assert _bits(second.state) == _bits(first.state)
    with first.strict_session():
        a = first.train_one_batch(staged=staged)
    with second.strict_session():
        b = second.train_one_batch(staged=second._stage_batch(batch, wait=True))
    assert _bits(a) == _bits(b) and _bits(second.state) == _bits(first.state)


def test_rows_are_the_documents_packed_end_to_end(tmp_path):
    """No padding, no gap: the rows in order are the stream of documents in
    the data set's seeded order, each ending in the end-of-document id."""
    lengths = np.asarray([5, 70, 3, 64, 9, 41], np.int64)
    ids = np.concatenate([np.r_[np.full(n - 1, i + 1), END_OF_DOCUMENT] for i, n in enumerate(lengths)]).astype(np.int32)
    np.savez(tmp_path / "documents.npz", ids=ids, lengths=lengths, order_seed=np.int64(4))
    ds = TokenDataset(DataConfig(dataset="tokens", seq_len=32, root_dir=str(tmp_path)))
    assert len(ds) == lengths.sum() // 32
    order = np.random.RandomState(4).permutation(len(lengths))
    stream = np.concatenate([np.split(ids, np.cumsum(lengths)[:-1])[i] for i in order])
    rows = np.stack([ds[i]["tokens"] for i in range(len(ds))])
    np.testing.assert_array_equal(rows.reshape(-1), stream[: rows.size])
    with pytest.raises(IndexError):
        ds[len(ds)]
    seeded = TokenDataset(DataConfig(dataset="tokens", seq_len=64, root_dir=""), id_rows=64, length=8)
    rows = np.stack([seeded[i]["tokens"] for i in range(len(seeded))])
    assert rows.min() == END_OF_DOCUMENT and rows.max() < 64 and len(seeded) >= 8


@pytest.mark.parametrize("what", ["evaluate", "load_eval_variables"])
def test_eval_predict_and_serve_refuse_a_sequence_model(tmp_path, what):
    cfg = get_config("trinity_tiny")
    with pytest.raises(ValueError, match="sequence model"):
        if what == "evaluate":
            cfg.require_detector("eval")
        else:
            from replication_faster_rcnn_tpu.train.trainer import load_eval_variables

            load_eval_variables(cfg, str(tmp_path))
    get_config("voc_resnet18").require_detector("eval")  # a detector passes


def test_a_sequence_model_config_refuses_the_detectors_backends():
    import dataclasses

    cfg = get_config("trinity_tiny")
    with pytest.raises(ValueError, match="train.backend='auto'"):
        cfg.replace(train=dataclasses.replace(cfg.train, backend="spmd"))
    with pytest.raises(ValueError, match="layer_types"):
        get_config("voc_resnet18").replace(data=DataConfig(dataset="tokens"))


def test_a_detector_run_imports_none_of_the_sequence_models_modules():
    """`setup_s` has no slack: building a detector's trainer and its step
    imports neither the sequence model, its two ops, the token data set nor
    the two kernel libraries they call."""
    code = (
        "import sys, jax\n"
        "from replication_faster_rcnn_tpu.config import get_config\n"
        "from replication_faster_rcnn_tpu.train import Trainer, create_train_state, make_optimizer, make_train_step\n"
        "import replication_faster_rcnn_tpu.cli\n"
        "cfg = get_config('voc_resnet18')\n"
        "tx, _ = make_optimizer(cfg, 1)\n"
        "from replication_faster_rcnn_tpu.train.train_step import model_kind\n"
        "kind = model_kind(cfg)\n"
        "model = kind.build(cfg)\n"
        "jax.eval_shape(lambda: create_train_state(cfg, jax.random.PRNGKey(0), tx)[1])\n"
        "make_train_step(model, cfg, tx)\n"
        "bad = [m for m in sys.modules if m.endswith(('models.lm', 'ops.attention', 'ops.grouped_mm', 'data.tokens'))"
        " or 'splash_attention' in m or 'megablox' in m]\n"
        "print('LOADED', bad)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    got = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=300)
    assert got.returncode == 0, got.stderr[-2000:]
    assert "LOADED []" in got.stdout, got.stdout
