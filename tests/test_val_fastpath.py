"""Val fast path (data.val_prepared): prepared eval
caches, the uint8 val wire, device-guidance eval preprocessing, and metric
parity against the plain (uncached) validation protocol.

The eval protocol is deterministic end to end (reference
train_pascal.py:135-145, 233-308), so the entire per-epoch val front —
decode, crop, resize, guidance, plus the full-res metric masks — is
cacheable.  What these tests pin down:

* the cached eval sample carries the evaluator's exact contract (wire keys
  + host-side ``gt``/``void_pixels``/``bbox``), with the full-res masks
  BIT-EXACT vs the plain pipeline (they feed the metric; rounding there
  would change reported Jaccards);
* the uint8 wire serves uint8 (the f32 semantic val batch is 25 MB);
* the end-to-end metric matches the plain path.
"""

import dataclasses

import numpy as np
import pytest

from distributedpytorch_tpu.data import (
    DataLoader,
    PreparedInstanceDataset,
    VOCInstanceSegmentation,
    build_eval_transform,
)
from distributedpytorch_tpu.data.pipeline import (
    build_prepared_eval_post_transform,
    build_prepared_semantic_eval_post_transform,
    build_semantic_eval_transform,
)
from distributedpytorch_tpu.data.prepared import PreparedSemanticDataset
from distributedpytorch_tpu.data.voc import VOCSemanticSegmentation


def make_base(root):
    return VOCInstanceSegmentation(root, split="val", transform=None,
                                   preprocess=True, area_thres=0)


@pytest.fixture()
def base(fake_voc_root):
    return make_base(fake_voc_root)


@pytest.fixture()
def plain(fake_voc_root):
    return VOCInstanceSegmentation(
        fake_voc_root, split="val", preprocess=True, area_thres=0,
        transform=build_eval_transform(crop_size=(64, 64), relax=10))


def make_eval_cache(base, tmp_path, uint8=False, guidance="nellipse_gaussians"):
    return PreparedInstanceDataset(
        base, str(tmp_path / "prep"), crop_size=(64, 64), relax=10,
        uint8_arrays=uint8, eval_protocol=True, max_im_size=(256, 256),
        post_transform=build_prepared_eval_post_transform(
            guidance=guidance, uint8_wire=uint8))


class TestInstanceEvalCache:
    def test_contract_vs_plain_pipeline(self, base, plain, tmp_path):
        ds = make_eval_cache(base, tmp_path)
        assert len(ds) == len(plain)
        for i in (0, 1, len(ds) - 1):
            got = ds[i]
            want = plain[i]
            # full-res metric masks: BIT-exact (they feed the Jaccard)
            np.testing.assert_array_equal(
                np.asarray(got["gt"], bool),
                np.asarray(want["gt"], bool).reshape(got["gt"].shape))
            np.testing.assert_array_equal(
                np.asarray(got["void_pixels"], bool),
                np.asarray(want["void_pixels"],
                           bool).reshape(got["void_pixels"].shape))
            np.testing.assert_array_equal(got["bbox"], want["bbox"])
            # crop_gt binary + exact; image within uint8 rounding
            np.testing.assert_array_equal(
                got["crop_gt"], np.asarray(want["crop_gt"], np.float32))
            assert got["concat"].shape == want["concat"].shape
            assert np.abs(got["concat"][..., :3]
                          - want["concat"][..., :3]).max() <= 0.5
            # guidance channel: same crop_gt in, same deterministic points
            # out — differences can only come from the rounded image (none)
            np.testing.assert_allclose(got["concat"][..., 3],
                                       want["concat"][..., 3],
                                       atol=1e-3)

    def test_second_access_never_touches_source(self, base, tmp_path):
        ds = make_eval_cache(base, tmp_path)
        ds.prebuild()
        first = ds[0]

        def boom(i):
            raise AssertionError("source dataset touched after prebuild")

        ds.dataset.__getitem__ = boom
        again = ds[0]
        np.testing.assert_array_equal(first["concat"], again["concat"])
        np.testing.assert_array_equal(first["gt"], again["gt"])

    def test_uint8_wire_dtypes(self, base, tmp_path):
        ds = make_eval_cache(base, tmp_path, uint8=True, guidance="none")
        s = ds[0]
        assert s["concat"].dtype == np.uint8 and s["concat"].shape[-1] == 3
        assert s["crop_gt"].dtype == np.uint8
        assert set(np.unique(s["crop_gt"])) <= {0, 1}

    def test_eval_cache_dir_distinct_from_train(self, base, tmp_path):
        train_ds = PreparedInstanceDataset(base, str(tmp_path / "prep"),
                                           crop_size=(64, 64), relax=10)
        eval_ds = make_eval_cache(base, tmp_path)
        assert train_ds.cache_dir != eval_ds.cache_dir

    def test_oversize_image_raises_with_guidance(self, base, tmp_path):
        ds = PreparedInstanceDataset(
            base, str(tmp_path / "prep"), crop_size=(64, 64), relax=10,
            eval_protocol=True, max_im_size=(8, 8),
            post_transform=build_prepared_eval_post_transform())
        with pytest.raises(ValueError, match="max_im_size"):
            ds[0]


class TestSemanticEvalCache:
    def test_contract_vs_plain_pipeline(self, fake_voc_root, tmp_path):
        base = VOCSemanticSegmentation(fake_voc_root, split="val",
                                       transform=None)
        plain = VOCSemanticSegmentation(
            fake_voc_root, split="val",
            transform=build_semantic_eval_transform(crop_size=(65, 65)))
        ds = PreparedSemanticDataset(
            base, str(tmp_path / "prep"), crop_size=(65, 65),
            post_transform=build_prepared_semantic_eval_post_transform())
        assert len(ds) == len(plain)
        for i in range(len(ds)):
            got, want = ds[i], plain[i]
            # class ids resized nearest: integer-exact
            np.testing.assert_array_equal(
                got["crop_gt"], np.asarray(want["crop_gt"], np.float32))
            assert np.abs(got["concat"] - want["concat"]).max() <= 0.5

    def test_uint8_wire_dtypes(self, fake_voc_root, tmp_path):
        base = VOCSemanticSegmentation(fake_voc_root, split="val",
                                       transform=None)
        ds = PreparedSemanticDataset(
            base, str(tmp_path / "prep"), crop_size=(65, 65),
            uint8_arrays=True,
            post_transform=build_prepared_semantic_eval_post_transform(
                uint8_wire=True))
        s = ds[0]
        assert s["concat"].dtype == np.uint8
        assert s["crop_gt"].dtype == np.uint8

    def test_fullres_gt_cached_exactly(self, fake_voc_root, tmp_path):
        """eval_full_res protocol: the native-resolution class-id mask is
        cached in padded uint8 rows and must come back BIT-exact (it is
        the metric's ground truth) alongside the resized wire keys."""
        base = VOCSemanticSegmentation(fake_voc_root, split="val",
                                       transform=None)
        plain = VOCSemanticSegmentation(
            fake_voc_root, split="val",
            transform=build_semantic_eval_transform(crop_size=(65, 65),
                                                    keep_fullres=True))
        ds = PreparedSemanticDataset(
            base, str(tmp_path / "prep"), crop_size=(65, 65),
            keep_fullres=True, max_im_size=(256, 256),
            post_transform=build_prepared_semantic_eval_post_transform())
        for i in range(len(ds)):
            got, want = ds[i], plain[i]
            np.testing.assert_array_equal(
                got["gt_full"],
                np.asarray(want["gt_full"],
                           np.uint8).reshape(got["gt_full"].shape))
        # distinct cache dir from the crop-res eval cache
        crop_only = PreparedSemanticDataset(
            base, str(tmp_path / "prep"), crop_size=(65, 65),
            post_transform=build_prepared_semantic_eval_post_transform())
        assert crop_only.cache_dir != ds.cache_dir

    def test_fullres_oversize_raises(self, fake_voc_root, tmp_path):
        base = VOCSemanticSegmentation(fake_voc_root, split="val",
                                       transform=None)
        ds = PreparedSemanticDataset(
            base, str(tmp_path / "prep"), crop_size=(65, 65),
            keep_fullres=True, max_im_size=(8, 8),
            post_transform=build_prepared_semantic_eval_post_transform())
        with pytest.raises(ValueError, match="val_max_im_size"):
            ds[0]


class TestTrainerIntegration:
    def _cfg(self, root, tmp_path, **over):
        from distributedpytorch_tpu.train import Config, apply_overrides
        cfg = apply_overrides(Config(), [
            f"data.root={root}", "data.train_batch=8", "data.val_batch=2",
            "data.crop_size=[64,64]", "data.relax=10", "data.area_thres=0",
            "model.backbone=resnet18", "model.output_stride=8",
            "optim.lr=1e-4", "checkpoint.async_save=false", "epochs=1",
            *[f"{k}={v}" for k, v in over.items()]])
        return dataclasses.replace(cfg, work_dir=str(tmp_path / "runs"))

    def test_val_metric_parity_plain_vs_prepared(self, fake_voc_root,
                                                 tmp_path):
        """Same state, same protocol: the prepared+uint8+device-guidance
        val path must reproduce the plain path's Jaccard to within the
        uint8 image rounding (<0.5/255 input perturbation)."""
        from distributedpytorch_tpu.train import Trainer

        tr_plain = Trainer(self._cfg(fake_voc_root, tmp_path / "a"))
        m_plain = tr_plain.validate(epoch=0)
        tr_fast = Trainer(self._cfg(
            fake_voc_root, tmp_path / "b",
            **{"data.prepared_cache": str(tmp_path / "cache"),
               "data.uint8_transfer": "true",
               "data.device_guidance": "true"}))
        # identical params: copy the plain trainer's state
        tr_fast.state = tr_plain.state
        m_fast = tr_fast.validate(epoch=0)
        assert m_fast["n_samples"] == m_plain["n_samples"]
        assert abs(m_fast["jaccard"] - m_plain["jaccard"]) < 2e-2
        for th in ("0.3", "0.5", "0.8"):
            assert abs(m_fast["jaccard_per_threshold"][th]
                       - m_plain["jaccard_per_threshold"][th]) < 2e-2
        tr_plain.close()
        tr_fast.close()

    @pytest.mark.slow  # tier-1 budget (PR 7): packbits val parity
    # (~9s); the packbits wire keeps its fast train-side gate in
    # test_prepared
    def test_val_parity_with_packed_mask_wire(self, fake_voc_root,
                                              tmp_path):
        """data.packbits_masks now rides the VAL wire too (1-bit crop_gt,
        unpacked inside the eval step): metrics must match the plain
        protocol like the unpacked fast path does."""
        from distributedpytorch_tpu.train import Trainer

        tr_plain = Trainer(self._cfg(fake_voc_root, tmp_path / "a"))
        m_plain = tr_plain.validate(epoch=0)
        tr_fast = Trainer(self._cfg(
            fake_voc_root, tmp_path / "b",
            **{"data.prepared_cache": str(tmp_path / "cache"),
               "data.uint8_transfer": "true",
               "data.device_guidance": "true",
               "data.packbits_masks": "true",
               "debug_asserts": "true"}))
        sample = tr_fast.val_set[0]
        h, w = tr_fast.cfg.data.crop_size
        assert sample["crop_gt"].shape == ((h * w + 7) // 8,)
        tr_fast.state = tr_plain.state
        m_fast = tr_fast.validate(epoch=0)
        assert abs(m_fast["jaccard"] - m_plain["jaccard"]) < 2e-2
        # the panels contract: the vis record must carry the UNPACKED
        # mask (the 1-bit wire row would crash make_val_panels silently)
        from distributedpytorch_tpu.train.evaluate import evaluate
        from distributedpytorch_tpu.train.logging import make_val_panels
        m = evaluate(tr_fast.eval_step, tr_fast.state, tr_fast.val_loader,
                     mesh=tr_fast.mesh, packed_masks=True)
        fb = m["_first_batch"]
        assert np.asarray(fb["batch"]["crop_gt"]).shape[1:] == (h, w)
        fig = make_val_panels(fb)
        assert fig is not None
        tr_plain.close()
        tr_fast.close()

    @pytest.mark.slow  # tier-1 budget (PR 18): two semantic fits
    # (~15s); the semantic eval cache keeps its fast contract gate
    # (TestSemanticEvalCache.test_contract_vs_plain_pipeline) and the
    # instance-task parity e2e stays in tier-1 above
    def test_semantic_val_parity(self, tmp_path):
        from distributedpytorch_tpu.data import make_fake_voc
        from distributedpytorch_tpu.train import Trainer

        fake_voc_root = make_fake_voc(str(tmp_path / "voc"), n_images=12,
                                      size=(96, 128), n_val=3, seed=3)
        sem = {"task": "semantic", "model.name": "deeplabv3",
               "model.nclass": 21, "model.in_channels": 3,
               "data.crop_size": "[65,65]"}
        tr_plain = Trainer(self._cfg(fake_voc_root, tmp_path / "a", **sem))
        m_plain = tr_plain.validate(epoch=0)
        tr_fast = Trainer(self._cfg(
            fake_voc_root, tmp_path / "b", **sem,
            **{"data.prepared_cache": str(tmp_path / "cache"),
               "data.uint8_transfer": "true"}))
        tr_fast.state = tr_plain.state
        m_fast = tr_fast.validate(epoch=0)
        assert abs(m_fast["miou"] - m_plain["miou"]) < 2e-2
        tr_plain.close()
        tr_fast.close()

    @pytest.mark.slow  # tier-1 budget (PR 7): TTA x prepared-val
    # composition (~12s); each half keeps its own fast gate
    def test_semantic_tta_composes_with_prepared_val(self, tmp_path):
        """Multi-scale + flip TTA reads the val batch host-side and
        re-forwards resized copies — it must compose with the uint8
        prepared val wire and match the plain path's TTA mIoU."""
        from distributedpytorch_tpu.data import make_fake_voc
        from distributedpytorch_tpu.train import Trainer

        fake_voc_root = make_fake_voc(str(tmp_path / "voc"), n_images=12,
                                      size=(96, 128), n_val=3, seed=9)
        sem = {"task": "semantic", "model.name": "deeplabv3",
               "model.nclass": 21, "model.in_channels": 3,
               "data.crop_size": "[65,65]",
               "eval_tta_scales": "[0.75,1.0]", "eval_tta_flip": "true"}
        tr_plain = Trainer(self._cfg(fake_voc_root, tmp_path / "a", **sem))
        m_plain = tr_plain.validate(epoch=0)
        tr_fast = Trainer(self._cfg(
            fake_voc_root, tmp_path / "b", **sem,
            **{"data.prepared_cache": str(tmp_path / "cache"),
               "data.uint8_transfer": "true"}))
        tr_fast.state = tr_plain.state
        m_fast = tr_fast.validate(epoch=0)
        assert abs(m_fast["miou"] - m_plain["miou"]) < 2e-2
        tr_plain.close()
        tr_fast.close()

    @pytest.mark.slow  # tier-1 budget (PR 10): fullres x prepared-val
    # composition (~7s); the fullres cache contract keeps its unit gate
    # (TestSemanticEvalCache.test_fullres_gt_cached_exactly) and the
    # crop-res prepared-val parity stays (test_semantic_val_parity)
    def test_semantic_fullres_val_parity(self, tmp_path):
        from distributedpytorch_tpu.data import make_fake_voc
        from distributedpytorch_tpu.train import Trainer

        fake_voc_root = make_fake_voc(str(tmp_path / "voc"), n_images=12,
                                      size=(96, 128), n_val=3, seed=5)
        sem = {"task": "semantic", "model.name": "deeplabv3",
               "model.nclass": 21, "model.in_channels": 3,
               "data.crop_size": "[65,65]", "eval_full_res": "true",
               "data.val_max_im_size": "[256,256]"}
        tr_plain = Trainer(self._cfg(fake_voc_root, tmp_path / "a", **sem))
        m_plain = tr_plain.validate(epoch=0)
        tr_fast = Trainer(self._cfg(
            fake_voc_root, tmp_path / "b", **sem,
            **{"data.prepared_cache": str(tmp_path / "cache"),
               "data.uint8_transfer": "true"}))
        tr_fast.state = tr_plain.state
        m_fast = tr_fast.validate(epoch=0)
        assert abs(m_fast["miou"] - m_plain["miou"]) < 2e-2
        tr_plain.close()
        tr_fast.close()

    @pytest.mark.slow  # tier-1 budget (PR 18): two full-res fits
    # (~15s); the device-warp wire keeps its fast gates
    # (TestSemanticEvalCache full-res contracts) and fullres parity
    # stays slow-gated (test_semantic_fullres_val_parity)
    def test_semantic_fullres_device_vs_host_path(self, tmp_path):
        """eval_device_fullres=true (device warp + uint8 class-map wire)
        must reproduce the host resize path's full-res mIoU through the
        real Trainer."""
        from distributedpytorch_tpu.data import make_fake_voc
        from distributedpytorch_tpu.train import Trainer

        fake_voc_root = make_fake_voc(str(tmp_path / "voc"), n_images=12,
                                      size=(96, 128), n_val=3, seed=13)
        sem = {"task": "semantic", "model.name": "deeplabv3",
               "model.nclass": 21, "model.in_channels": 3,
               "data.crop_size": "[65,65]", "eval_full_res": "true",
               "data.val_max_im_size": "[256,256]"}
        tr_host = Trainer(self._cfg(fake_voc_root, tmp_path / "a", **sem,
                                    eval_device_fullres="false"))
        m_host = tr_host.validate(epoch=0)
        tr_dev = Trainer(self._cfg(fake_voc_root, tmp_path / "b", **sem,
                                   eval_device_fullres="true"))
        tr_dev.state = tr_host.state
        m_dev = tr_dev.validate(epoch=0)
        # same protocol arithmetic on device; only f32-association /
        # argmax-tie noise may move individual boundary pixels
        assert abs(m_dev["miou"] - m_host["miou"]) < 1e-3
        assert m_dev["n_samples"] == m_host["n_samples"]
        tr_host.close()
        tr_dev.close()

    def test_instance_bf16_readback_parity(self, fake_voc_root, tmp_path):
        """eval_bf16_probs now also halves the instance val logit D2H:
        bf16 logit rounding may flip boundary pixels at the thresholds but
        must not move the Jaccard beyond noise."""
        from distributedpytorch_tpu.train import Trainer

        tr = Trainer(self._cfg(fake_voc_root, tmp_path / "a"))
        m_bf16 = tr.validate(epoch=0)          # default: bf16 readback
        tr.cfg = dataclasses.replace(tr.cfg, eval_bf16_probs=False)
        m_f32 = tr.validate(epoch=0)
        assert abs(m_bf16["jaccard"] - m_f32["jaccard"]) < 1e-2
        tr.close()

    @pytest.mark.slow  # tier-1 budget (PR 20): overlap is opt-in and its
    # fit smoke is ~24s; fast gate:
    # test_val_prepared_off_keeps_plain_path (default path stays tier-1)
    def test_val_overlap_smoke(self, fake_voc_root, tmp_path):
        """Thin tier-1 smoke: one overlapped fit completes with a val
        entry per epoch and a best checkpoint.  The serial-vs-overlap
        curve-parity A/B (two 3-epoch fits, ~25s) is the `slow` variant
        below."""
        import glob

        from distributedpytorch_tpu.train import Trainer

        tr = Trainer(self._cfg(fake_voc_root, tmp_path / "ov",
                               **{"epochs": 2, "val_overlap": "true"}))
        hist = tr.fit()
        tr.close()
        assert len(hist["val"]) == 2
        assert all(np.isfinite(v["jaccard"]) for v in hist["val"])
        assert glob.glob(str(tmp_path / "ov" / "**" / "best*"),
                         recursive=True), "no best checkpoint"

    @pytest.mark.slow
    def test_val_overlap_matches_serial_fit(self, fake_voc_root, tmp_path):
        """val_overlap runs each validation concurrently with the next
        train epoch.  The evaluated states are identical to the serial
        schedule (training never waits on val), so the val curves must
        match; best-checkpoint gating must also land."""
        import glob

        from distributedpytorch_tpu.train import Trainer

        hists = {}
        for mode, flag in (("serial", "false"), ("overlap", "true")):
            tr = Trainer(self._cfg(fake_voc_root, tmp_path / mode,
                                   **{"epochs": 3,
                                      "val_overlap": flag}))
            hists[mode] = tr.fit()
            tr.close()
            assert glob.glob(str(tmp_path / mode / "**" / "best*"),
                             recursive=True), f"{mode}: no best checkpoint"
        assert len(hists["overlap"]["val"]) == \
            len(hists["serial"]["val"]) == 3
        for a, b in zip(hists["serial"]["val"], hists["overlap"]["val"]):
            assert abs(a["jaccard"] - b["jaccard"]) < 1e-5
        assert hists["serial"]["train_loss"] == pytest.approx(
            hists["overlap"]["train_loss"], abs=1e-6)

    def test_val_prepared_off_keeps_plain_path(self, fake_voc_root,
                                               tmp_path):
        from distributedpytorch_tpu.train import Trainer

        tr = Trainer(self._cfg(
            fake_voc_root, tmp_path,
            **{"data.prepared_cache": str(tmp_path / "cache"),
               "data.val_prepared": "false",
               "data.uint8_transfer": "true",
               "data.device_guidance": "true"}))
        assert not isinstance(tr.val_set, PreparedInstanceDataset)
        tr.close()
