"""Every shipped preset (the five BASELINE.json configs + coco_vgg16) must
build and run one train step — catches config-level wiring gaps (anchor
counts, head widths, class counts, roi ops) that per-module tests with
hand-rolled tiny configs cannot."""

import dataclasses

import jax
import numpy as np
import pytest

from replication_faster_rcnn_tpu.config import (
    CONFIGS,
    DataConfig,
    MeshConfig,
    ProposalConfig,
    get_config,
)
from replication_faster_rcnn_tpu.data import SyntheticDataset
from replication_faster_rcnn_tpu.data.loader import collate
from replication_faster_rcnn_tpu.train.train_step import (
    create_train_state,
    make_optimizer,
    make_train_step,
)


@pytest.mark.parametrize(
    "name",
    [
        # each preset costs a full train-step compile (1-3 min on one CPU
        # core): the flagship stays in the fast tier as the smoke preset,
        # the rest are slow-tier (pytest -m slow runs them all)
        n if n == "voc_resnet18" else pytest.param(n, marks=pytest.mark.slow)
        for n in sorted(CONFIGS)
    ],
)
def test_preset_one_train_step(name):
    cfg = get_config(name)
    # shrink to CPU-tractable shapes; everything config-specific (backbone,
    # fpn, roi op, anchor spec, class count) stays as the preset defines it
    cfg = cfg.replace(
        data=DataConfig(dataset="synthetic", image_size=(64, 64), max_boxes=8),
        train=dataclasses.replace(cfg.train, batch_size=2),
        mesh=MeshConfig(num_data=1),
        model=dataclasses.replace(cfg.model, compute_dtype="float32"),
        proposals=ProposalConfig(pre_nms_train=256, post_nms_train=64),
        roi_targets=dataclasses.replace(cfg.roi_targets, n_sample=16),
    )
    tx, _ = make_optimizer(cfg, steps_per_epoch=10)
    model, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
    ds = SyntheticDataset(cfg.data, length=2)
    batch = collate([ds[0], ds[1]])
    step = jax.jit(make_train_step(model, cfg, tx))
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(jax.device_get(metrics["loss"]))), name
    assert int(new_state.step) == 1


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_config_json_round_trip(name):
    """config_from_dict rebuilds every preset exactly after a JSON round
    trip."""
    import json

    from replication_faster_rcnn_tpu.config import config_from_dict

    cfg = get_config(name)
    rebuilt = config_from_dict(json.loads(json.dumps(dataclasses.asdict(cfg))))
    assert rebuilt == cfg
