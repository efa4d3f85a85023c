"""Test configuration: force an 8-device virtual CPU mesh.

Must set XLA flags before jax initializes — this is the standard JAX idiom for
exercising multi-device pjit/shard_map paths without TPU hardware
(SURVEY.md §4).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persist EVERY compiled program, not only the slow ones: the suite compiles
# hundreds of tiny CPU programs that cost 50-500 ms of XLA each and repeat
# identically run to run — below any per-program threshold, minutes in
# aggregate.  Set through JAX's own variable so the children the chaos, fleet
# and CLI tests spawn inherit it; chip runs keep JAX's default threshold.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from distributedpytorch_tpu.backend_health import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from distributedpytorch_tpu.data import make_fake_voc  # noqa: E402


@pytest.fixture(scope="session")
def fake_voc_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("fake_voc")
    return make_fake_voc(str(root), n_images=6, size=(120, 160), n_val=2, seed=0)


def assert_grads_close(g0, g1, rel: float = 5e-4, frob: float = 1e-5):
    """Scale-aware gradient parity (the PR 7 remat idiom, shared by the
    remat and pallas-backward tests): every leaf's inf-norm diff bounded
    by ``rel`` x that leaf's own gradient scale, AND the whole tree's
    Frobenius-norm diff by ``frob`` x the tree's norm — catches a single
    corrupted leaf and broad systematic drift while tolerating XLA's
    reassociation of recomputed forwards."""
    leaves0 = jax.tree.leaves(g0)
    leaves1 = jax.tree.leaves(g1)
    assert len(leaves0) == len(leaves1)
    sq0 = sqd = 0.0
    for a, b in zip(leaves0, leaves1):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        scale = max(float(np.abs(a).max()), 1.0)
        worst = float(np.abs(a - b).max())
        assert worst <= rel * scale, (
            f"leaf diff {worst:.3e} vs scale {scale:.3e} "
            f"(rel {worst / scale:.3e} > {rel})")
        sq0 += float((a ** 2).sum())
        sqd += float(((a - b) ** 2).sum())
    assert sqd ** 0.5 <= frob * max(sq0 ** 0.5, 1e-30), (
        f"tree-wide relative diff {(sqd ** 0.5) / (sq0 ** 0.5):.3e} "
        f"> {frob}")


def _make_serve_predictor(guidance_inject: str):
    import jax
    import optax

    from distributedpytorch_tpu.models import build_model
    from distributedpytorch_tpu.parallel import create_train_state
    from distributedpytorch_tpu.predict import Predictor

    model = build_model("danet", nclass=1, backbone="resnet18",
                        output_stride=8, guidance_inject=guidance_inject)
    state = create_train_state(jax.random.PRNGKey(0), model,
                               optax.sgd(1e-3), (1, 64, 64, 4))
    return Predictor(model, state.params, state.batch_stats,
                     resolution=(64, 64), relax=10)


@pytest.fixture(scope="session")
def serve_stem_predictor():
    """ONE stem (whole-forward) serve predictor per test session: the
    predictor's jit cache holds the bucket ladder's compiled programs —
    the heaviest compile-bearing fixture of the serve modules — and the
    telemetry/lowering + jaxaudit trace caches key on the fn identity,
    so sharing the instance across modules shares every one of those
    compiles instead of re-paying them per module.  Tests that COUNT
    compiles or monkeypatch forwards build their own private
    predictors."""
    return _make_serve_predictor("stem")


@pytest.fixture(scope="session")
def serve_split_predictor():
    """The session-serving (encode/decode split) sibling, same sharing
    rationale — two compiled stages per bucket make it twice as
    compile-heavy as the stem ladder."""
    return _make_serve_predictor("head")


@pytest.fixture()
def rng():
    return np.random.default_rng(1234)


@pytest.fixture()
def interpreted_kernels(monkeypatch):
    """Run the Pallas attention kernels in the pallas interpreter for
    tests that reach them through a model: CPU has no Mosaic compiler and
    the kernels take ``interpret`` only as an explicit argument.  The
    DANet modules and the token models' attention blocks look the entry
    points up at call time, so patching the module attributes is enough."""
    import functools

    from distributedpytorch_tpu.ops import pallas_attention as pa

    for name in ("flash_position_attention", "flash_channel_attention",
                 "flash_causal_attention", "flash_sparse_attention",
                 "flash_index_align_terms", "flash_indexer_scores",
                 "flash_indexer_scores_grads", "flash_topk_keep",
                 "flash_block_diffusion_attention"):
        monkeypatch.setattr(
            pa, name, functools.partial(getattr(pa, name), interpret=True))


@pytest.fixture(params=["einsum", "kernels"])
def attn_form(request, monkeypatch):
    """The attention form a block runs: the einsum form, or the flash
    kernels in the pallas interpreter."""
    from distributedpytorch_tpu.models import danet

    if request.param == "kernels":
        request.getfixturevalue("interpreted_kernels")
        monkeypatch.setattr(danet, "auto_wants_flash", lambda dtype: True)
    return request.param


@pytest.fixture(scope="session", autouse=True)
def _threadsan_witness():
    """DPTPU_THREADSAN=1 arms the jaxrace runtime witness for the whole
    session: the pinned guard map (tests/contracts/threads.json) is
    installed over the live classes, declared locks become witnesses,
    and every guarded attribute write is checked against the writing
    thread's held set.  The under-load serve/swap tests then validate
    the STATIC guard map against real schedules — teardown fails the
    session on any recorded violation.  Off by default: instrumented
    ``__setattr__`` costs a dict probe per write."""
    if os.environ.get("DPTPU_THREADSAN") != "1":
        yield
        return
    import json

    from distributedpytorch_tpu.analysis import threadsan
    from distributedpytorch_tpu.analysis.race import threads_contract_path

    pin = threads_contract_path(
        os.path.join(os.path.dirname(__file__), "contracts"))
    with open(pin, encoding="utf-8") as fh:
        contract = json.load(fh)
    installed = threadsan.install(contract)
    try:
        yield
    finally:
        violations = threadsan.violations()
        threadsan.uninstall()
        assert not violations, (
            f"threadsan: {len(violations)} unguarded write(s) to "
            f"declared-guarded attributes (instrumented: {installed}):\n"
            + "\n".join(
                f"  {v['class']}.{v['attr']} (guard {v['lock']}) "
                f"on thread {v['thread']}" for v in violations[:10]))
