"""Device-resident dataset cache (`data/device_cache.py`): the cached
feed path must be indistinguishable from the host loader pipeline —
same samples, same augmentation decisions, same step outputs.

Reference counterpart: none (the torch DataLoader re-ships every batch,
`frcnn.py:19-23`); this is the TPU-native feed for a transfer-bound
host->device link.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replication_faster_rcnn_tpu.config import (
    DataConfig,
    FasterRCNNConfig,
    MeshConfig,
    ModelConfig,
    TrainConfig,
)
from replication_faster_rcnn_tpu.data import SyntheticDataset
from replication_faster_rcnn_tpu.data.augment import AugmentedView
from replication_faster_rcnn_tpu.data.device_cache import (
    CachedSampler,
    DeviceCache,
    materialize_batch,
)
from replication_faster_rcnn_tpu.data.loader import DataLoader, collate
from replication_faster_rcnn_tpu.train import (
    create_train_state,
    make_cached_train_step,
    make_optimizer,
    make_train_step,
)

N, H, W = 12, 64, 64
SEED, EPOCH, BATCH = 3, 2, 4


def _data_cfg(**kw):
    return DataConfig(dataset="synthetic", image_size=(H, W), max_boxes=8, **kw)


def _dataset(**kw):
    return SyntheticDataset(_data_cfg(**kw), length=N)


def _sampler(ds, cache, **kw):
    kw.setdefault("batch_size", BATCH)
    kw.setdefault("seed", SEED)
    s = CachedSampler(len(ds), cache.image_hw, **kw)
    s.set_epoch(EPOCH)
    return s


def _host_batch(ds, idxs, hflip=False, scale_range=None):
    view = AugmentedView(
        ds, SEED, EPOCH, hflip=hflip, scale_range=scale_range,
        scale_on_device=scale_range is not None,
    )
    return collate([view[int(i)] for i in idxs])


class TestMaterializeEquivalence:
    """materialize_batch == the host device-mode pipeline, key by key."""

    idxs = np.asarray([0, 5, 7, 11])

    def _compare(self, host, dev):
        for k in host:
            np.testing.assert_allclose(
                np.asarray(dev[k]), host[k], atol=2e-3, err_msg=k
            )

    def test_no_augment(self):
        ds = _dataset()
        cache = DeviceCache(ds)
        sel = _sampler(ds, cache).selection(self.idxs)
        self._compare(_host_batch(ds, self.idxs), materialize_batch(cache.arrays, sel))

    def test_flip_only(self):
        ds = _dataset()
        cache = DeviceCache(ds)
        sel = _sampler(ds, cache, hflip=True).selection(self.idxs)
        assert sel["flip"].any(), "fixture must exercise at least one flip"
        self._compare(
            _host_batch(ds, self.idxs, hflip=True),
            materialize_batch(cache.arrays, sel),
        )

    def test_flip_and_jitter(self):
        ds = _dataset()
        cache = DeviceCache(ds)
        sel = _sampler(
            ds, cache, hflip=True, scale_range=(0.75, 1.25)
        ).selection(self.idxs)
        assert sel["jitter"].shape == (len(self.idxs), 4)
        host = _host_batch(ds, self.idxs, hflip=True, scale_range=(0.75, 1.25))
        self._compare(host, materialize_batch(cache.arrays, sel))

    def test_identity_jitter_preserves_subpixel_gt_box(self):
        """Regression: a raw GT box that is already <1px must survive a
        jitter draw resolving to identity geometry (h, w, 0, 0) — the host
        path skips jitter_boxes entirely there, so the device path must not
        apply its <1px collapse. A real (non-identity) draw still collapses
        it."""
        cache = {
            "image": jnp.zeros((1, H, W, 3), jnp.float32),
            "boxes": jnp.asarray(
                [[[10.0, 10.0, 10.4, 20.0],  # 0.4px tall raw GT box
                  [5.0, 5.0, 25.0, 30.0]]], jnp.float32
            ),
            "labels": jnp.asarray([[1, 2]], jnp.int32),
            "mask": jnp.asarray([[True, True]]),
        }
        ident = {
            "idx": jnp.asarray([0], jnp.int32),
            "jitter": jnp.asarray([[H, W, 0, 0]], jnp.int32),
        }
        out = materialize_batch(cache, ident)
        np.testing.assert_array_equal(np.asarray(out["labels"]), [[1, 2]])
        np.testing.assert_allclose(
            np.asarray(out["boxes"]), np.asarray(cache["boxes"])
        )
        np.testing.assert_array_equal(np.asarray(out["mask"]), [[True, True]])

        real = {
            "idx": jnp.asarray([0], jnp.int32),
            "jitter": jnp.asarray([[H + 2, W + 2, 1, 1]], jnp.int32),
        }
        out2 = materialize_batch(cache, real)
        labels2 = np.asarray(out2["labels"])
        assert labels2[0, 0] == -1  # sub-pixel box collapsed by a real draw
        assert not np.asarray(out2["mask"])[0, 0]
        assert labels2[0, 1] == 2  # the normal box survives the same draw

    def test_uint8_samples(self):
        ds = _dataset(device_normalize=True)
        cache = DeviceCache(ds)
        assert cache.arrays["image"].dtype == jnp.uint8
        sel = _sampler(ds, cache, hflip=True).selection(self.idxs)
        host = _host_batch(ds, self.idxs, hflip=True)
        dev = materialize_batch(cache.arrays, sel)
        np.testing.assert_array_equal(np.asarray(dev["image"]), host["image"])


class TestSampler:
    def test_process_shards_union_to_global_selection(self):
        """Multi-process sampler: per-rank selections are contiguous
        blocks of the SAME global order, and flip decisions key on the
        GLOBAL sample index — so the assembled global batch is identical
        on any topology."""
        n = 24
        whole = CachedSampler(n, (64, 64), batch_size=8, seed=SEED,
                              hflip=True, shuffle=True)
        ranks = [
            CachedSampler(n, (64, 64), batch_size=8, seed=SEED, hflip=True,
                          shuffle=True, process_index=r, process_count=2)
            for r in range(2)
        ]
        for s in [whole] + ranks:
            s.set_epoch(EPOCH)
        assert len(ranks[0]) == len(whole)  # __len__ stays GLOBAL
        whole_sels = list(whole)
        rank_sels = [list(s) for s in ranks]
        for step, sel in enumerate(whole_sels):
            for r in range(2):
                rsel = rank_sels[r][step]
                assert rsel["idx"].shape == (4,)
                np.testing.assert_array_equal(
                    rsel["idx"], sel["idx"][r * 4 : r * 4 + 4]
                )
                np.testing.assert_array_equal(
                    rsel["flip"], sel["flip"][r * 4 : r * 4 + 4]
                )

    def test_process_sharding_validation(self):
        with pytest.raises(ValueError, match="process_count"):
            CachedSampler(8, (64, 64), batch_size=8, seed=SEED,
                          process_index=3, process_count=2)
        with pytest.raises(ValueError, match="divide"):
            CachedSampler(8, (64, 64), batch_size=6, seed=SEED,
                          process_index=0, process_count=4)

    def test_epoch_order_matches_dataloader(self):
        ds = _dataset()
        loader = DataLoader(ds, batch_size=BATCH, shuffle=True, seed=SEED,
                            num_workers=0)
        loader.set_epoch(EPOCH)
        cache_order = []
        s = _sampler(ds, DeviceCache(ds), shuffle=True)
        for sel in s:
            cache_order.extend(sel["idx"].tolist())
        np.testing.assert_array_equal(
            np.asarray(cache_order), loader._order()[: len(cache_order)]
        )

    def test_len_drops_last(self):
        ds = _dataset()
        s = _sampler(ds, DeviceCache(ds), batch_size=5)
        assert len(s) == N // 5
        assert sum(1 for _ in s) == len(s)

    def test_byte_guard(self):
        ds = _dataset()
        with pytest.raises(ValueError, match="device cache"):
            DeviceCache(ds, max_bytes=1024)


def _tiny_cfg(**data_kw):
    return FasterRCNNConfig(
        model=ModelConfig(backbone="resnet18", roi_op="align",
                          compute_dtype="float32"),
        data=_data_cfg(**data_kw),
        train=TrainConfig(batch_size=BATCH, n_epoch=2),
        mesh=MeshConfig(num_data=1),
    )


class TestCachedStep:
    # tier rebalance: one full-step-compile variant is enough for the
    # 870s fast-tier budget on a single-core box; the no-augment variant
    # still runs in the slow tier (tier_budget_audit.py).
    @pytest.mark.parametrize(
        "aug", [pytest.param(False, marks=pytest.mark.slow), True]
    )
    def test_cached_step_matches_fed_step(self, aug):
        """One optimizer step through the cache == the same step fed the
        identical host batch (the whole point of the feature)."""
        kw = dict(hflip=True, scale_range=(0.75, 1.25)) if aug else {}
        cfg = _tiny_cfg()
        ds = SyntheticDataset(cfg.data, length=N)
        cache = DeviceCache(ds)
        sampler = _sampler(ds, cache, **kw)
        sel = next(iter(sampler))
        host = _host_batch(
            ds, sel["idx"],
            hflip=kw.get("hflip", False), scale_range=kw.get("scale_range"),
        )

        tx, _ = make_optimizer(cfg, steps_per_epoch=3)
        model, state0 = create_train_state(cfg, jax.random.PRNGKey(0), tx)
        fed = jax.jit(make_train_step(model, cfg, tx))
        cached = jax.jit(make_cached_train_step(model, cfg, tx))

        _, m_fed = fed(state0, {k: jnp.asarray(v) for k, v in host.items()})
        _, m_cached = cached(
            state0, cache.arrays, {k: jnp.asarray(v) for k, v in sel.items()}
        )
        for k in m_fed:
            np.testing.assert_allclose(
                float(m_fed[k]), float(m_cached[k]), rtol=2e-4, atol=2e-5,
                err_msg=k,
            )
        # the telemetry health scalars ride the same metrics dict — sanity
        # on a healthy step, piggybacked here to spare the fast tier
        # another full-step compile
        from replication_faster_rcnn_tpu.telemetry.health import HEALTH_KEYS

        assert set(HEALTH_KEYS) <= set(m_fed)
        assert float(m_fed["grad_norm"]) > 0
        assert int(m_fed["nonfinite_count"]) == 0
        np.testing.assert_allclose(
            float(m_fed["update_ratio"]),
            float(m_fed["update_norm"]) / float(m_fed["param_norm"]),
            rtol=1e-4,
        )

    @pytest.mark.slow
    def test_trainer_cache_device_end_to_end(self, tmp_path):
        """Trainer(cache_device=True) trains, checkpoints, and its loss
        agrees with the loader-fed Trainer on the same (seed, epoch)."""
        from replication_faster_rcnn_tpu.train.trainer import Trainer

        cfg = _tiny_cfg(cache_device=True, augment_hflip=True)
        ds = SyntheticDataset(cfg.data, length=N)
        tr = Trainer(cfg, workdir=str(tmp_path / "cached"), dataset=ds)
        assert tr.device_cache is not None and tr.loader is None
        out_cached = tr.train(log_every=1)

        cfg_fed = _tiny_cfg(augment_hflip=True)
        tr_fed = Trainer(cfg_fed, workdir=str(tmp_path / "fed"), dataset=ds)
        out_fed = tr_fed.train(log_every=1)
        np.testing.assert_allclose(
            out_cached["loss"], out_fed["loss"], rtol=2e-4, atol=2e-5
        )

    def test_spmd_backend_rejected(self):
        from replication_faster_rcnn_tpu.train.trainer import Trainer

        cfg = _tiny_cfg(cache_device=True).replace(
            train=TrainConfig(batch_size=BATCH, n_epoch=2, backend="spmd")
        )
        ds = SyntheticDataset(cfg.data, length=N)
        with pytest.raises(ValueError, match="cache_device"):
            Trainer(cfg, dataset=ds)

    def test_multiprocess_runtime_rejected(self, monkeypatch):
        """A multi-host runtime must fail loudly before the cache upload:
        one process cannot place a replicated dataset across a multi-host
        mesh, and a cryptic device_put error 5 GB in is the wrong way to
        learn that."""
        from replication_faster_rcnn_tpu.train.trainer import Trainer

        monkeypatch.setattr(jax, "process_count", lambda: 2)
        cfg = _tiny_cfg(cache_device=True)
        ds = SyntheticDataset(cfg.data, length=N)
        with pytest.raises(ValueError, match="single-process"):
            Trainer(cfg, dataset=ds)


class TestCLISurfaces:
    @pytest.mark.slow
    def test_train_steps_mode_with_cache_device(self, tmp_path, capsys):
        """--steps N must iterate the index sampler, not the (None)
        loader, in cache_device mode."""
        from replication_faster_rcnn_tpu import cli

        rc = cli.main(
            [
                "train", "--dataset", "synthetic", "--image-size", "64",
                "--batch-size", "2", "--steps", "2", "--cache-device",
                "--workdir", str(tmp_path),
            ]
        )
        assert rc == 0


@pytest.mark.slow
class TestCachedStepDP8:
    def test_dp8_matches_single_device(self):
        """The cached step under an 8-device data mesh computes the same
        update as on one device: cache replicated, sel sharded, gathers
        local (no collectives beyond the usual grad allreduce)."""
        from replication_faster_rcnn_tpu.parallel import make_mesh, shard_batch
        from replication_faster_rcnn_tpu.parallel.mesh import replicated

        cfg1 = _tiny_cfg()
        cfg8 = dataclasses.replace(cfg1, mesh=MeshConfig(num_data=8),
                                   train=TrainConfig(batch_size=8, n_epoch=2))
        cfg1 = dataclasses.replace(cfg1, train=TrainConfig(batch_size=8,
                                                           n_epoch=2))
        ds = SyntheticDataset(cfg1.data, length=N)

        metrics = {}
        for name, cfg in [("dp1", cfg1), ("dp8", cfg8)]:
            mesh = make_mesh(cfg.mesh)
            cache = DeviceCache(ds, mesh=mesh)
            sampler = CachedSampler(
                len(ds), cache.image_hw, batch_size=8, seed=SEED,
                hflip=True, scale_range=(0.75, 1.25),
            )
            sampler.set_epoch(EPOCH)
            sel = next(iter(sampler))
            tx, _ = make_optimizer(cfg, steps_per_epoch=3)
            model, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
            state = jax.device_put(state, replicated(mesh))
            step = jax.jit(make_cached_train_step(model, cfg, tx))
            _, m = step(state, cache.arrays, shard_batch(sel, mesh, cfg.mesh))
            metrics[name] = {k: float(v) for k, v in m.items()}
        for k in metrics["dp1"]:
            np.testing.assert_allclose(
                metrics["dp1"][k], metrics["dp8"][k], rtol=2e-4, atol=2e-5,
                err_msg=k,
            )
