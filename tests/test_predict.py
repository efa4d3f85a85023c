"""Inference path: clicks -> guidance -> forward -> full-res paste-back.

The reference shipped no inference entry point (its val loop was the only
consumer of the trained model, reference train_pascal.py:233-308); predict.py
completes that story, so these tests pin its contracts: preprocessing parity
with the val transform pipeline, output geometry, and the CLI body.
"""

import numpy as np
import pytest

from distributedpytorch_tpu.data import transforms as T
from distributedpytorch_tpu.predict import (
    Predictor,
    SemanticPredictor,
    guidance_from_points,
    parse_points,
    prepare_input,
)


def _image(h=90, w=120, seed=0):
    return np.random.RandomState(seed).randint(
        0, 256, (h, w, 3)).astype(np.uint8)


def _points(w=120, h=90):
    # left, right, top, bottom extremes of a central object
    return np.array([[30.0, 45.0], [95.0, 40.0], [60.0, 20.0], [55.0, 75.0]])


class TestPrepareInput:
    def test_shapes_and_ranges(self):
        concat, bbox = prepare_input(_image(), _points(), relax=10,
                                     resolution=(64, 64))
        assert concat.shape == (64, 64, 4)
        assert concat.dtype == np.float32
        assert concat.min() >= 0.0 and concat.max() <= 255.0
        # guidance channel peaks at exactly 255 (driver input contract,
        # reference train_pascal.py:188)
        assert concat[..., 3].max() == pytest.approx(255.0)
        # bbox covers the points expanded by relax
        x0, y0, x1, y1 = bbox
        pts = _points()
        assert x0 <= pts[:, 0].min() - 10 + 1 and x1 >= pts[:, 0].max() + 9
        assert y0 <= pts[:, 1].min() - 10 + 1 and y1 >= pts[:, 1].max() + 9

    def test_guidance_matches_val_transform(self):
        """Clicks at the gt's deterministic extreme points must produce the
        same guidance map the val pipeline computes from the gt itself."""
        h = w = 48
        gt = np.zeros((h, w), np.float32)
        gt[10:38, 14:42] = 1.0
        from distributedpytorch_tpu.data.guidance import extreme_points_fixed
        pts = extreme_points_fixed(gt, 0).astype(np.float64)
        expected = T.NEllipseWithGaussians(alpha=0.6, is_val=True)(
            {"crop_gt": gt})["nellipseWithGaussians"]
        got = guidance_from_points((h, w), pts, alpha=0.6)
        np.testing.assert_allclose(got, expected, atol=1e-4)

    def test_guidance_families_match_transforms(self):
        """Each selectable family reproduces its training transform's map
        when the clicks are the gt's deterministic extreme points."""
        h = w = 48
        gt = np.zeros((h, w), np.float32)
        gt[10:38, 14:42] = 1.0
        from distributedpytorch_tpu.data.guidance import extreme_points_fixed
        pts = extreme_points_fixed(gt, 0).astype(np.float64)
        np.testing.assert_allclose(
            guidance_from_points((h, w), pts, family="nellipse"),
            T.NEllipse(is_val=True)({"crop_gt": gt})["nellipse"], atol=1e-4)
        np.testing.assert_allclose(
            guidance_from_points((h, w), pts, family="extreme_points"),
            T.ExtremePoints(pert=0, elem="crop_gt", is_val=True)(
                {"crop_gt": gt})["extreme_points"], atol=1e-4)
        with pytest.raises(ValueError, match="unknown guidance"):
            guidance_from_points((h, w), pts, family="bogus")

    def test_rejects_bad_inputs(self):
        with pytest.raises(ValueError, match="RGB"):
            prepare_input(np.zeros((8, 8)), _points())
        with pytest.raises(ValueError, match="4 xy"):
            prepare_input(_image(), np.zeros((3, 2)))
        with pytest.raises(ValueError, match="outside"):
            prepare_input(_image(), np.array([[0, 0], [1, 1], [2, 2],
                                              [500, 500]]))


class TestParsePoints:
    def test_formats(self):
        a = parse_points("1,2 3,4 5,6 7,8")
        b = parse_points("1,2;3,4;5,6;7,8")
        np.testing.assert_array_equal(a, b)
        assert a.shape == (4, 2)

    def test_errors(self):
        with pytest.raises(ValueError):
            parse_points("1,2 3,4")
        with pytest.raises(ValueError):
            parse_points("1,2 3,4 5,6 seven,8")


def _tiny_predictor(res=64):
    import jax
    import optax

    from distributedpytorch_tpu.models import build_model
    from distributedpytorch_tpu.parallel import create_train_state

    model = build_model("danet", nclass=1, backbone="resnet18",
                        output_stride=8)
    state = create_train_state(jax.random.PRNGKey(0), model,
                               optax.sgd(1e-3), (1, res, res, 4))
    return model, state, Predictor(model, state.params, state.batch_stats,
                                   resolution=(res, res), relax=10)


class TestPredictor:
    def test_full_res_probability_mask(self):
        _, _, p = _tiny_predictor()
        img = _image()
        prob = p.predict(img, _points())
        assert prob.shape == img.shape[:2]
        assert prob.dtype == np.float32
        assert 0.0 <= prob.min() and prob.max() <= 1.0

    def test_relax_border_shaved(self):
        """Predictions outside the un-padded click box are zero (the val
        metric's mask_relax paste-back, reference train_pascal.py:290)."""
        _, _, p = _tiny_predictor()
        prob = p.predict(_image(), _points())
        pts = _points()
        x0, y0 = pts[:, 0].min(), pts[:, 1].min()
        x1, y1 = pts[:, 0].max(), pts[:, 1].max()
        outside = np.ones_like(prob, bool)
        outside[int(y0):int(y1) + 1, int(x0):int(x1) + 1] = False
        assert prob[outside].max() == 0.0

    def test_predict_batch_matches_singles(self):
        """N objects in one dispatch == N single predicts, exactly."""
        _, _, p = _tiny_predictor()
        img = _image()
        pts_a = _points()
        pts_b = pts_a + np.array([5.0, -3.0])
        batched = p.predict_batch(img, [pts_a, pts_b])
        assert len(batched) == 2
        # batch-size-dependent XLA fusion order gives float32 ulp-level
        # differences; semantically identical
        np.testing.assert_allclose(batched[0], p.predict(img, pts_a),
                                   atol=1e-5)
        np.testing.assert_allclose(batched[1], p.predict(img, pts_b),
                                   atol=1e-5)
        assert p.predict_batch(img, []) == []

    def test_mesh_sharded_batch_matches_single_device(self):
        """Distributed inference: crops sharded over the 8-device mesh give
        the same masks as the unsharded predictor (incl. the pad-to-device-
        count path for N not divisible by the mesh size)."""
        from distributedpytorch_tpu.parallel import make_mesh

        model, state, p_single = _tiny_predictor()
        # (data=4, model=2): the batch pads/shards over the 4-wide data
        # axis only, not the full 8-device count
        mesh = make_mesh(data=4, model=2)
        p_mesh = Predictor(model, state.params, state.batch_stats,
                           resolution=(64, 64), relax=10, mesh=mesh)
        img = _image()
        pts = [_points(), _points() + np.array([4.0, 2.0]),
               _points() + np.array([-3.0, 1.0])]  # 3 % 8 != 0: pad path
        got = p_mesh.predict_batch(img, pts)
        want = p_single.predict_batch(img, pts)
        assert len(got) == 3
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=1e-5)

    def test_deterministic_and_reusable(self):
        _, _, p = _tiny_predictor()
        img = _image()
        a = p.predict(img, _points())
        b = p.predict(img, _points())
        np.testing.assert_array_equal(a, b)
        # different image through the same compiled forward
        c = p.predict(_image(seed=1), _points())
        assert c.shape == a.shape


class TestModelFromConfig:
    def test_forwards_every_danet_model_knob(self):
        """Inference must rebuild the model the Trainer trained —
        including pam_score_dtype (a silent train/predict numeric
        divergence otherwise)."""
        import jax.numpy as jnp

        from distributedpytorch_tpu.predict import model_from_config
        from distributedpytorch_tpu.train import Config
        cfg = Config()
        cfg.model.backbone = "resnet18"
        cfg.model.pam_score_dtype = "bfloat16"
        cfg.model.pam_block_size = 7
        m = model_from_config(cfg)
        assert m.pam_score_dtype == jnp.bfloat16
        assert m.pam_block_size == 7

    def test_a_token_run_is_rebuilt_from_its_own_lm_config(self, tmp_path):
        """``model.lm_config`` reaches the rebuilt model: a JSON file that
        is not the ``tiny`` preset is not served as ``tiny``."""
        import json

        from distributedpytorch_tpu.models import build_model
        from distributedpytorch_tpu.models.nemotron_h import PRESETS
        from distributedpytorch_tpu.predict import model_from_config
        from distributedpytorch_tpu.train import Config, apply_overrides
        path = tmp_path / "wider.json"
        path.write_text(json.dumps(dict(PRESETS["tiny"], vocab_size=384)))
        cfg = apply_overrides(Config(), [
            "task=tokens", "model.name=nemotron_h",
            f"model.lm_config={path}"])
        m = model_from_config(cfg)
        assert m == build_model("nemotron_h", lm_config=str(path),
                                remat=False)
        assert m.vocab_size == 384 != PRESETS["tiny"]["vocab_size"]

    def test_bn_stat_dtype_carries_over(self):
        from distributedpytorch_tpu.predict import model_from_config
        from distributedpytorch_tpu.train import Config, apply_overrides
        cfg = apply_overrides(Config(), ["model.backbone=resnet18",
                                         "model.bn_fp32_stats=false"])
        assert model_from_config(cfg).bn_fp32_stats is False

    def test_a_config_saved_before_a_field_existed(self):
        """A stand-in that carries only the old fields gets the missing
        ones' own defaults; ring PAM is served by the einsum form."""
        import types

        from distributedpytorch_tpu.predict import model_from_config
        old = types.SimpleNamespace(model=types.SimpleNamespace(
            name="danet", nclass=1, backbone="resnet18",
            output_stride=None, dtype="float32", pam_block_size=None,
            pam_impl="ring", remat=False, moe_experts=0, moe_hidden=None,
            moe_k=1, moe_capacity_factor=1.25, aux_head=False))
        m = model_from_config(old)
        assert m.pam_impl == "einsum" and m.bn_fp32_stats is True
        assert m.guidance_inject == "stem"


class TestFromTorch:
    def test_roundtrip_matches_native_predictor(self, tmp_path):
        """A torch .pth exported from this framework's own params serves
        identical predictions through Predictor.from_torch."""
        import jax
        import torch

        from distributedpytorch_tpu.train import Config
        from distributedpytorch_tpu.utils.torch_interop import (
            params_to_torch_state_dict,
        )

        res = 64
        cfg = Config()
        cfg.model.backbone = "resnet18"
        cfg.data.crop_size = (res, res)
        cfg.data.relax = 10
        from distributedpytorch_tpu.predict import model_from_config
        model = model_from_config(cfg)
        variables = model.init(jax.random.PRNGKey(3),
                               np.zeros((1, res, res, 4), np.float32),
                               train=False)
        sd = params_to_torch_state_dict(variables["params"],
                                        variables["batch_stats"])
        pth = tmp_path / "export.pth"
        torch.save({k: torch.from_numpy(np.asarray(v)) for k, v in sd.items()},
                   str(pth))

        p_torch = Predictor.from_torch(str(pth), cfg=cfg)
        p_native = Predictor(model, variables["params"],
                             variables["batch_stats"],
                             resolution=(res, res), relax=10)
        img = _image()
        np.testing.assert_allclose(p_torch.predict(img, _points()),
                                   p_native.predict(img, _points()),
                                   atol=1e-5)

    @pytest.mark.slow  # tier-1 budget (PR 7): torch-script export
    # roundtrip (~11s); torch interop stays fast-gated in
    # test_torch_interop
    def test_export_torch_script_roundtrip(self, tmp_path):
        """run dir -> scripts/export_torch.py -> .pth -> from_torch gives
        the same predictions as from_run (full interop loop)."""
        import os
        import subprocess
        import sys

        import jax

        from distributedpytorch_tpu.models import build_model
        from distributedpytorch_tpu.parallel import create_train_state
        from distributedpytorch_tpu.train import Config, config as config_lib
        from distributedpytorch_tpu.train.checkpoint import CheckpointManager
        from distributedpytorch_tpu.train.optim import make_optimizer

        res = 64
        cfg = Config()
        cfg.model.backbone = "resnet18"
        cfg.data.crop_size = (res, res)
        cfg.data.relax = 10
        run = tmp_path / "run_0"
        run.mkdir()
        config_lib.to_json(cfg, str(run / "config.json"))
        model = build_model("danet", nclass=1, backbone="resnet18",
                            output_stride=8)
        tx, _ = make_optimizer(cfg.optim, total_steps=1)
        state = create_train_state(jax.random.PRNGKey(5), model, tx,
                                   (1, res, res, 4))
        mgr = CheckpointManager(str(run / "checkpoints"), async_save=False)
        mgr.save(0, state, metric=0.2)
        mgr.close()

        pth = tmp_path / "export.pth"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "scripts", "export_torch.py"),
             str(run), str(pth)],
            capture_output=True, text=True, timeout=600,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert r.returncode == 0, r.stderr[-1500:]
        assert pth.exists() and "exported" in r.stdout

        img = _image()
        np.testing.assert_allclose(
            Predictor.from_torch(str(pth), cfg=cfg).predict(img, _points()),
            Predictor.from_run(str(run)).predict(img, _points()),
            atol=1e-5)

    def test_zero_match_raises(self, tmp_path):
        import torch

        from distributedpytorch_tpu.train import Config

        cfg = Config()
        cfg.model.backbone = "resnet18"
        cfg.data.crop_size = (64, 64)
        pth = tmp_path / "junk.pth"
        torch.save({"foo.weight": torch.zeros(3, 3)}, str(pth))
        with pytest.raises(ValueError, match="imported 0"):
            Predictor.from_torch(str(pth), cfg=cfg, partial=True)


class TestPredictCli:
    def test_end_to_end_from_run_dir(self, tmp_path):
        """Round-trip: save a tiny run (config.json + checkpoint), then
        segment a PNG through the CLI body."""
        import jax
        from PIL import Image

        from distributedpytorch_tpu.models import build_model
        from distributedpytorch_tpu.parallel import create_train_state
        from distributedpytorch_tpu.predict import predict_cli
        from distributedpytorch_tpu.train import Config, config as config_lib
        from distributedpytorch_tpu.train.checkpoint import CheckpointManager
        from distributedpytorch_tpu.train.optim import make_optimizer

        res = 64
        cfg = Config()
        cfg.model.backbone = "resnet18"
        cfg.data.crop_size = (res, res)
        cfg.data.relax = 10
        run = tmp_path / "run_0"
        run.mkdir()
        config_lib.to_json(cfg, str(run / "config.json"))

        model = build_model("danet", nclass=1, backbone="resnet18",
                            output_stride=8)
        tx, _ = make_optimizer(cfg.optim, total_steps=1)
        state = create_train_state(jax.random.PRNGKey(0), model,
                                   tx, (1, res, res, 4))
        mgr = CheckpointManager(str(run / "checkpoints"), async_save=False)
        mgr.save(0, state, metric=0.5)
        mgr.close()

        img_path = tmp_path / "img.png"
        Image.fromarray(_image()).save(img_path)
        out_path = tmp_path / "mask.png"
        overlay_path = tmp_path / "overlay.png"
        summary = predict_cli(str(run), str(img_path),
                              "30,45 95,40 60,20 55,75", str(out_path),
                              overlay_path=str(overlay_path))
        assert out_path.exists() and overlay_path.exists()
        mask = np.asarray(Image.open(out_path))
        assert mask.shape == (90, 120)
        assert set(np.unique(mask)) <= {0, 255}
        assert summary["pixels"] == int((mask == 255).sum())

        # an instance run without points must fail loudly, not segment
        with pytest.raises(ValueError, match="--points"):
            predict_cli(str(run), str(img_path), None, str(out_path))

    def test_from_run_restores_moe_param_tree(self, tmp_path):
        """MoE options shape the param tree; from_run must rebuild the model
        with them or the Orbax restore structure-mismatches."""
        import jax

        from distributedpytorch_tpu.models import build_model
        from distributedpytorch_tpu.parallel import create_train_state
        from distributedpytorch_tpu.train import Config, config as config_lib
        from distributedpytorch_tpu.train.checkpoint import CheckpointManager
        from distributedpytorch_tpu.train.optim import make_optimizer

        res = 64
        cfg = Config()
        cfg.model.backbone = "resnet18"
        cfg.model.moe_experts = 2
        cfg.data.crop_size = (res, res)
        cfg.data.relax = 10
        run = tmp_path / "run_moe"
        run.mkdir()
        config_lib.to_json(cfg, str(run / "config.json"))
        model = build_model("danet", nclass=1, backbone="resnet18",
                            output_stride=8, moe_experts=2)
        tx, _ = make_optimizer(cfg.optim, total_steps=1)
        state = create_train_state(jax.random.PRNGKey(0), model, tx,
                                   (1, res, res, 4))
        mgr = CheckpointManager(str(run / "checkpoints"), async_save=False)
        mgr.save(0, state, metric=0.1)
        mgr.close()

        p = Predictor.from_run(str(run))
        prob = p.predict(_image(), _points())
        assert prob.shape == (90, 120)

    def test_cli_rejects_training_flags_in_predict_mode(self, capsys):
        from distributedpytorch_tpu.__main__ import main

        with pytest.raises(SystemExit):
            main(["--predict", "img.png", "--run-dir", "r", "--points",
                  "1,1 2,2 3,3 4,4", "optim.lr=1e-3"])
        assert "config.json" in capsys.readouterr().err

    @pytest.mark.slow  # tier-1 budget (PR 20): semantic run-dir CLI
    # roundtrip (~8s); fast gate: test_end_to_end_from_run_dir +
    # TestSerializedExport::test_instance_roundtrip_symbolic_batch
    def test_semantic_run_roundtrip(self, tmp_path):
        """A semantic-task run dir predicts a whole-image class map, both
        through SemanticPredictor and the task-dispatching CLI body."""
        import jax
        from PIL import Image

        from distributedpytorch_tpu.models import build_model
        from distributedpytorch_tpu.parallel import create_train_state
        from distributedpytorch_tpu.predict import predict_cli
        from distributedpytorch_tpu.train import Config, config as config_lib
        from distributedpytorch_tpu.train.checkpoint import CheckpointManager
        from distributedpytorch_tpu.train.optim import make_optimizer

        res, nclass = 64, 7
        cfg = Config()
        cfg.task = "semantic"
        cfg.model.name = "deeplabv3"
        cfg.model.nclass = nclass
        cfg.model.backbone = "resnet18"
        cfg.model.output_stride = 16
        cfg.model.in_channels = 3
        cfg.data.crop_size = (res, res)
        run = tmp_path / "run_sem"
        run.mkdir()
        config_lib.to_json(cfg, str(run / "config.json"))
        model = build_model("deeplabv3", nclass=nclass, backbone="resnet18",
                            output_stride=16)
        tx, _ = make_optimizer(cfg.optim, total_steps=1)
        state = create_train_state(jax.random.PRNGKey(0), model, tx,
                                   (1, res, res, 3))
        mgr = CheckpointManager(str(run / "checkpoints"), async_save=False)
        mgr.save(0, state, metric=0.1)
        mgr.close()

        p = SemanticPredictor.from_run(str(run))
        classes = p.predict(_image())
        assert classes.shape == (90, 120) and classes.dtype == np.uint8
        assert classes.max() < nclass

        # the instance Predictor must refuse this run
        with pytest.raises(ValueError, match="instance"):
            Predictor.from_run(str(run))

        # CLI dispatch: no --points needed for a semantic run
        img_path = tmp_path / "img.png"
        Image.fromarray(_image()).save(img_path)
        out_path = tmp_path / "classes.png"
        summary = predict_cli(str(run), str(img_path), None, str(out_path))
        assert summary["task"] == "semantic"
        saved = np.asarray(Image.open(out_path))
        np.testing.assert_array_equal(saved, classes)
        assert summary["classes"]  # per-class pixel counts present

        # clicks/threshold on a semantic run error loudly, never drop
        with pytest.raises(ValueError, match="do not apply"):
            predict_cli(str(run), str(img_path), "1,1 2,2 3,3 4,4",
                        str(out_path))
        with pytest.raises(ValueError, match="do not apply"):
            predict_cli(str(run), str(img_path), None, str(out_path),
                        threshold=0.9)

    def test_from_run_rejects_incompatible_configs(self, tmp_path):
        from distributedpytorch_tpu.train import Config, config as config_lib

        for overrides, msg in [
            ({"task": "semantic", "model_nclass": 21}, "task"),
            ({"guidance": "none"}, "guidance"),
        ]:
            run = tmp_path / f"run_{msg}"
            run.mkdir()
            cfg = Config()
            if "task" in overrides:
                cfg.task = overrides["task"]
                cfg.model.nclass = overrides["model_nclass"]
            if "guidance" in overrides:
                cfg.data.guidance = overrides["guidance"]
            config_lib.to_json(cfg, str(run / "config.json"))
            with pytest.raises(ValueError, match=msg):
                Predictor.from_run(str(run))


class TestSlidingWindow:
    """SemanticPredictor mode='slide': full-resolution tiled inference."""

    def _predictor(self, res=64, nclass=7):
        import jax

        from distributedpytorch_tpu.models import build_model
        from distributedpytorch_tpu.parallel import create_train_state
        from distributedpytorch_tpu.predict import SemanticPredictor

        model = build_model("deeplabv3", nclass=nclass, backbone="resnet18",
                            output_stride=16)
        import optax
        state = create_train_state(jax.random.PRNGKey(0), model,
                                   optax.sgd(1e-3), (1, res, res, 3))
        return SemanticPredictor(model, state.params, state.batch_stats,
                                 resolution=(res, res))

    def test_crop_sized_image_matches_resize_mode(self):
        # at exactly crop size both modes see the identical single window
        p = self._predictor()
        rng = np.random.default_rng(0)
        img = rng.uniform(0, 255, (64, 64, 3)).astype(np.float32)
        np.testing.assert_array_equal(p.predict(img, mode="resize"),
                                      p.predict(img, mode="slide"))

    def test_larger_image_full_resolution_output(self):
        p = self._predictor()
        rng = np.random.default_rng(1)
        img = rng.uniform(0, 255, (96, 150, 3)).astype(np.float32)
        out = p.predict(img, mode="slide", overlap=0.5)
        assert out.shape == (96, 150)
        assert out.max() < 7
        # deterministic: same windows, same average
        np.testing.assert_array_equal(
            out, p.predict(img, mode="slide", overlap=0.5))

    def test_smaller_image_pads_and_crops_back(self):
        p = self._predictor()
        img = np.random.default_rng(2).uniform(
            0, 255, (40, 50, 3)).astype(np.float32)
        out = p.predict(img, mode="slide")
        assert out.shape == (40, 50)

    def test_bad_mode_and_overlap_raise(self):
        p = self._predictor()
        img = np.zeros((64, 64, 3), np.float32)
        with pytest.raises(ValueError, match="unknown mode"):
            p.predict(img, mode="tiles")
        with pytest.raises(ValueError, match="overlap"):
            p.predict(img, mode="slide", overlap=1.0)

    def test_hit_normalization_no_seams(self):
        # stub the per-window probs with a constant one-hot: whatever the
        # overlap pattern, the averaged argmax must be that class at every
        # pixel — seams would mean the hit-count normalization is wrong
        p = self._predictor()
        onehot = np.zeros((1, 64, 64, 7), np.float32)
        onehot[..., 3] = 1.0
        p._forward_probs = lambda x: onehot
        img = np.zeros((100, 130, 3), np.float32)
        out = p.predict(img, mode="slide", overlap=0.25)
        assert (out == 3).all()


class TestSlideInstanceGuard:
    def test_instance_run_rejects_slide(self, tmp_path, monkeypatch):
        from PIL import Image

        from distributedpytorch_tpu import predict as predict_mod
        from distributedpytorch_tpu.train import Config

        img_path = tmp_path / "img.png"
        Image.fromarray(np.zeros((32, 32, 3), np.uint8)).save(img_path)
        monkeypatch.setattr(predict_mod, "load_run_config",
                            lambda run_dir: Config())  # task='instance'
        with pytest.raises(ValueError, match="--slide does not apply"):
            predict_mod.predict_cli("unused", str(img_path),
                                    "1,1 2,2 3,3 4,4", str(tmp_path / "o.png"),
                                    slide=True)


class TestSerializedExport:
    """jax.export / StableHLO deployment artifacts (export_serialized)."""

    def test_instance_roundtrip_symbolic_batch(self, tmp_path):
        from distributedpytorch_tpu.predict import (
            export_serialized,
            load_serialized,
        )
        _, _, p = _tiny_predictor()
        path = str(tmp_path / "danet.stablehlo")
        info = export_serialized(p, path)   # symbolic batch, cpu+tpu
        assert info["bytes"] > 0 and info["input_shape"][0] == "b"
        fn = load_serialized(path)
        r = np.random.RandomState(0)
        for b in (1, 3):                    # one artifact, several batches
            x = r.uniform(0, 255, (b, 64, 64, 4)).astype(np.float32)
            got = np.asarray(fn(x))
            want = np.asarray(p._forward(x))
            assert got.shape == want.shape
            np.testing.assert_allclose(got, want, atol=1e-5)

    def test_semantic_roundtrip_fixed_batch(self, tmp_path):
        from distributedpytorch_tpu.predict import (
            export_serialized,
            load_serialized,
        )
        p = TestSlidingWindow._predictor(TestSlidingWindow())
        path = str(tmp_path / "deeplab.stablehlo")
        info = export_serialized(p, path, batch=2)
        assert info["input_shape"][0] == "2"
        fn = load_serialized(path)
        x = np.random.RandomState(1).uniform(
            0, 255, (2, *p.resolution, 3)).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(fn(x)),
                                      np.asarray(p._forward(x)))

    def test_mesh_predictor_refused(self, tmp_path):
        import jax

        from distributedpytorch_tpu.parallel import make_mesh
        from distributedpytorch_tpu.predict import export_serialized
        model, state, _ = _tiny_predictor()
        mesh = make_mesh()
        p = Predictor(model, state.params, state.batch_stats,
                      resolution=(64, 64), relax=10, mesh=mesh)
        with pytest.raises(ValueError, match="mesh"):
            export_serialized(p, str(tmp_path / "x.bin"))
