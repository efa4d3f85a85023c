"""Compile for the chip without a chip.

The CPU suite never reaches the Mosaic kernels (``attention_impl=auto``
picks einsum off-TPU) and runs them only through the pallas interpreter,
so nothing in it can see a kernel Mosaic refuses or a multi-device program
that cannot lower.  libtpu compiles for a topology it does not have:
``get_topology_desc("v5e:2x2")`` gives four compile-only ``TPU v5 lite``
devices, and everything below is lowered and compiled for them — the
kernels at the flagship's head shape, and the train and eval steps over
the 4-device data mesh with the kernels live.  A compile check, not a run:
``chip_smoke.py`` is the run.

One process only: libtpu holds ``/tmp/libtpu_lockfile`` even compile-only.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributedpytorch_tpu.models import build_model
from distributedpytorch_tpu.models import danet as danet_mod
from distributedpytorch_tpu.ops import pallas_attention as pa
from distributedpytorch_tpu.parallel import (
    create_train_state,
    make_eval_step,
    make_train_step,
)
from distributedpytorch_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from distributedpytorch_tpu.telemetry import scopes
from distributedpytorch_tpu.train.precision import precision_policy


@pytest.fixture(scope="module")
def v5e_mesh():
    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # no libtpu in this environment
        pytest.skip(f"no compile-only TPU topology: {e}")
    devices = np.asarray(topo.devices)
    assert devices.size == 4 and devices[0].device_kind == "TPU v5 lite"
    return Mesh(devices.reshape(4, 1), (DATA_AXIS, MODEL_AXIS))


def _one_chip(mesh) -> NamedSharding:
    """Replicated on the described topology's first chip."""
    return NamedSharding(Mesh(mesh.devices[:1], (DATA_AXIS, MODEL_AXIS)), P())


def _custom_calls(hlo: str) -> list[str]:
    """The HLO instructions that ARE Mosaic kernel calls."""
    return [ln for ln in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


#: the forward's Mosaic calls (flash PAM, CAM energy, CAM apply) and, in a
#: differentiated program, the PAM reverse pass's one fused sweep (64 and
#: 4,096 tokens both keep the image's dQ resident: ``_bwd_plan``)
FORWARD_CALLS = 3
TRAIN_CALLS = FORWARD_CALLS + 1


def _assert_kernels_local(hlo: str, rows: int, n_calls: int):
    """``n_calls`` Mosaic calls, each on ``rows`` batch rows, none fed by an
    all-gather."""
    calls = _custom_calls(hlo)
    assert len(calls) == n_calls, f"{len(calls)} tpu_custom_call(s)"
    gathered = {m.group(1) for m in
                re.finditer(r"(%[\w.\-]+) = [^\n]*\ball-gather", hlo)}
    for ln in calls:
        out_shape = re.search(r"= \(?\w+\[(\d+),", ln)
        assert out_shape and int(out_shape.group(1)) == rows, ln[:160]
        operands = set(re.findall(r"%[\w.\-]+", ln.split("custom-call(", 1)[1]))
        assert not operands & gathered, ln[:160]


def test_kernels_compile_at_flagship_head_shape(v5e_mesh):
    """B8 · N4096 · C512 bf16 — the DANet-R101 os8 512² head on one chip:
    forward and value_and_grad of both kernels through Mosaic."""
    one = _one_chip(v5e_mesh)
    b, n, c = 8, 4096, 512
    qk = jax.ShapeDtypeStruct((b, n, c // 8), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((b, n, c), jnp.bfloat16, sharding=one)

    def both(q, k, v):
        return (pa.flash_position_attention(q, k, v).astype(jnp.float32).sum()
                + pa.flash_channel_attention(v).astype(jnp.float32).sum())

    fwd = jax.jit(both).lower(qk, qk, v).compile().as_text()
    assert len(_custom_calls(fwd)) == FORWARD_CALLS
    grad = jax.jit(jax.value_and_grad(both, argnums=(0, 1, 2))).lower(
        qk, qk, v).compile().as_text()
    calls = _custom_calls(grad)
    assert len(calls) == TRAIN_CALLS
    assert sum("%pam_bwd_fused" in ln.split(" = ")[0] for ln in calls) == 1
    # the flash backward: no token-pair (N x N) array of any dtype reaches
    # HBM, and the scan that used to rebuild the forward is gone
    assert not re.search(r"\[(\d+,)?4096,4096\]", grad)
    under_bwd = [s for s in scopes.scope_table(grad).values()
                 if scopes.PAM_BWD in s.path.split("/")]
    assert "custom-call" in {s.opcode for s in under_bwd}
    assert "while" not in {s.opcode for s in under_bwd}


@pytest.mark.parametrize("tokens", [300, 65536])
def test_reverse_pass_compiles_off_the_flagship_shape(v5e_mesh, tokens):
    """300 tokens (no tile multiple: one padded, key-masked 384-tile) and
    65,536 (a 2,048-squared crop at os8: the image's dQ no longer fits the
    resident budget, so the two sweeps run) through Mosaic."""
    one = _one_chip(v5e_mesh)
    qk = jax.ShapeDtypeStruct((1, tokens, 64), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((1, tokens, 512), jnp.bfloat16, sharding=one)

    def pam(q, k, v):
        return pa.flash_position_attention(q, k, v).astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(pam, argnums=(0, 1, 2))).lower(
        qk, qk, v).compile().as_text()
    reverse = sorted(re.findall(r"%(pam_bwd\w*?)(?:\.\d+)? = ",
                                "\n".join(_custom_calls(hlo))))
    _, fused = pa._bwd_plan(tokens, 64)
    assert reverse == (
        ["pam_bwd_fused"] if fused else ["pam_bwd_dkv", "pam_bwd_dq"])


@pytest.fixture(scope="module")
def r18_bf16(v5e_mesh):
    """The bf16 policy and optimizer for DANet-r18 os8 at 64², with
    ``auto`` resolving as it does on a TPU host for the module's tests."""
    policy = precision_policy("bfloat16")
    tx = optax.sgd(1e-3, momentum=0.9)
    mp = pytest.MonkeyPatch()
    mp.setattr(danet_mod, "_on_tpu", lambda: True)
    try:
        yield policy, tx
    finally:
        mp.undo()


def _model_and_state(mesh, tx, cross_replica: bool):
    model = build_model(
        "danet", nclass=1, backbone="resnet18", output_stride=8,
        dtype=jnp.bfloat16,
        bn_cross_replica_axis=DATA_AXIS if cross_replica else None)
    assert model.pam_impl == "auto" and model.cam_impl == "auto"
    shapes = jax.eval_shape(lambda: create_train_state(
        jax.random.PRNGKey(0), model, tx, (1, 64, 64, 4)))
    repl = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=repl),
        shapes)
    return model, state


def _batch(mesh, rows: int):
    data = NamedSharding(mesh, P(DATA_AXIS))
    return {
        "concat": jax.ShapeDtypeStruct((rows, 64, 64, 4), jnp.float32,
                                       sharding=data),
        "crop_gt": jax.ShapeDtypeStruct((rows, 64, 64), jnp.float32,
                                        sharding=data),
    }


@pytest.mark.parametrize("reduce_buckets", [0, 4])
def test_train_step_compiles_on_four_chips(v5e_mesh, r18_bf16,
                                           reduce_buckets):
    """The GSPMD step (the Config default) and the bucketed shard_map
    step both lower with the kernels on B/4 rows."""
    policy, tx = r18_bf16
    model, state = _model_and_state(v5e_mesh, tx,
                                    cross_replica=bool(reduce_buckets))
    step = make_train_step(model, tx, mesh=v5e_mesh, precision=policy,
                           reduce_buckets=reduce_buckets)
    hlo = step.lower(state, _batch(v5e_mesh, 8)).compile().as_text()
    _assert_kernels_local(hlo, rows=2, n_calls=TRAIN_CALLS)
    assert "all-reduce" in hlo  # the gradient reduction is still there


def test_eval_step_compiles_on_four_chips(v5e_mesh, r18_bf16):
    _, tx = r18_bf16
    model, state = _model_and_state(v5e_mesh, tx, cross_replica=False)
    ev = make_eval_step(model, mesh=v5e_mesh)
    hlo = ev.lower(state, _batch(v5e_mesh, 8)).compile().as_text()
    _assert_kernels_local(hlo, rows=2, n_calls=FORWARD_CALLS)


def test_init_compiles_on_four_chips(v5e_mesh, r18_bf16):
    """``create_train_state`` traces the forward on a 1-row dummy with no
    context mesh.  That is sound only because the parameters do not depend
    on the forward, so it is dead code by the time Mosaic would refuse a
    4-device program — this is the guard for that assumption."""
    _, tx = r18_bf16
    model = build_model("danet", nclass=1, backbone="resnet18",
                        output_stride=8, dtype=jnp.bfloat16)
    init = jax.jit(lambda: create_train_state(
        jax.random.PRNGKey(0), model, tx, (1, 64, 64, 4), mesh=v5e_mesh))
    assert "tpu_custom_call" not in init.lower().compile().as_text()


def test_latent_moe_block_compiles_at_published_widths(v5e_mesh):
    """One LatentMoE block of the benchmark's token configuration (hidden
    4,096, latent 1,024, experts 2,688 wide, 8 of 512 held, top-22) over
    8,192 tokens in bfloat16, forward and reverse: the dropless grouped
    product (``jax.lax.ragged_dot``) and its two transposes go through the
    TPU compiler's own grouped-matmul calls, over one chunk of the row
    buffer at a time: nothing wide is made at the worst-case buffer's size
    (8,192 x 8 rows), and nothing of it is an (N, E, C) dispatch tensor."""
    import json
    import os

    from distributedpytorch_tpu.models import nemotron_h as nh
    from distributedpytorch_tpu.parallel import moe as moe_lib

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "nemotron3_super_stage_tp8_ep64.json")) as f:
        cfg = nh.LMConfig.from_dict(json.load(f))
    one = _one_chip(v5e_mesh)
    layer = nh.LatentMoE(cfg, jnp.bfloat16)
    u = jax.ShapeDtypeStruct((1, 8192, cfg.hidden_size), jnp.bfloat16,
                             sharding=one)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.hidden_size),
                                             jnp.bfloat16)))["params"])

    def loss(p, v):
        out = layer.apply({"params": p}, v, mutable=["counters"])[0]
        return out.astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, u).compile().as_text()
    grouped = [ln for ln in _custom_calls(hlo) if "ragged" in ln.lower()]
    # two products forward, and each one's two transposes
    assert len(grouped) >= 6, len(grouped)
    assert re.search(rf"bf16\[{moe_lib.CHUNK_ROWS},{cfg.expert_hidden}\]",
                     hlo)
    rows = moe_lib.dropless_buffer_rows(8192, cfg.experts_per_token,
                                        cfg.experts_held)
    assert rows == 8192 * cfg.experts_held
    assert not re.search(
        rf"\[{rows},({cfg.latent_size}|{cfg.expert_hidden})\]", hlo)
    assert not re.search(r"\[8192,512,\d+\]", hlo)


@pytest.mark.parametrize("q_heads", [4, 16])
def test_causal_attention_block_compiles_at_the_cells_shape(
        v5e_mesh, monkeypatch, q_heads):
    """The token model's attention block with the causal flash kernels live,
    forward and reverse, in bfloat16 over 1 x 8,192 tokens: the benchmark
    cell's share (4 query heads to 1 key/value head of 128) and the
    published group (16 query heads to one key/value head).  One forward
    call and one fused reverse call; no token-pair array of any dtype, and
    no key/value head repeated for its group."""
    import json
    import os

    from distributedpytorch_tpu.models import nemotron_h as nh

    monkeypatch.setattr(danet_mod, "_on_tpu", lambda: True)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "nemotron3_super_stage_tp8_ep64.json")) as f:
        cfg = json.load(f)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"]) == (4, 1, 128)
    cfg = nh.LMConfig.from_dict(dict(cfg, num_attention_heads=q_heads))
    one = _one_chip(v5e_mesh)
    layer = nh.Attention(cfg, jnp.bfloat16)
    seq = 8192
    u = jax.ShapeDtypeStruct((1, seq, cfg.hidden_size), jnp.bfloat16,
                             sharding=one)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.hidden_size),
                                             jnp.bfloat16)))["params"])

    def loss(p, v):
        return layer.apply({"params": p}, v).astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, u).compile().as_text()
    names = sorted(ln.split(" = ")[0].strip().lstrip("%").split(".")[0]
                   for ln in _custom_calls(hlo))
    assert names == [scopes.CAUSAL_ATTN, scopes.CAUSAL_ATTN_BWD_FUSED]
    assert not re.search(rf"\[(\d+,)*{seq},{seq}\]", hlo)
    # the group reads its one key/value head through the index map: the
    # calls take the queries' heads and ONE key/value row
    for ln in _custom_calls(hlo):
        operands = ln.split("operand_layout_constraints=", 1)[1]
        assert f"bf16[{q_heads},{seq},128]" in operands, ln[:300]
        assert operands.count(f"bf16[1,{seq},128]") == 2, ln[:300]


def test_causal_attention_calls_resolve_to_their_block(v5e_mesh, monkeypatch):
    """A small token model's differentiated forward with the kernels live,
    compiled for the chip: four Mosaic calls — per attention layer the
    forward call and the fused reverse call; the block's recomputation runs
    no second forward call, it keeps the first one's output and
    log-sum-exp — and the executable's scope table puts the trunk layer's
    two under ``attn`` and the prediction module's under ``mtp``, none
    under ``other``.  The benchmark's ``attn_kernel_roofline`` pattern reads
    all four by their event names and DANet's ``pam`` patterns read none."""
    import json
    import os

    from distributedpytorch_tpu.models import nemotron_h as nh

    monkeypatch.setattr(danet_mod, "_on_tpu", lambda: True)
    one = _one_chip(v5e_mesh)
    model = nh.build_nemotron_h(
        dict(nh.PRESETS["tiny"], head_dim=128, hidden_size=128),
        dtype=jnp.bfloat16, remat=True)
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"])

    def loss(p, t):
        (logits, mtp), _ = model.apply({"params": p}, t, train=True,
                                       mutable=["counters"])
        return logits.sum() + mtp.sum()

    hlo = jax.jit(jax.grad(loss)).lower(params, tokens).compile().as_text()
    table = scopes.scope_table(hlo)
    calls = {ln.split(" = ")[0].strip().lstrip("%") for ln in
             _custom_calls(hlo)}
    calls = {c for c in calls if c.startswith(scopes.CAUSAL_ATTN)}
    by_layer = {}
    for c in calls:
        s = table[c]
        by_layer.setdefault(s.layer, []).append(
            (c.split(".")[0], s.phase))
    want = [(scopes.CAUSAL_ATTN, "fwd"),
            (scopes.CAUSAL_ATTN_BWD_FUSED, "bwd")]
    assert {k: sorted(v) for k, v in by_layer.items()} == {
        scopes.ATTN: want, scopes.MTP: want}
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def pattern(metric):
        with open(os.path.join(here, "benchmarks", "metrics",
                               metric + ".json")) as f:
            return re.compile(json.load(f)["args"]["event_pattern"])

    events = [f"%{c} custom-call" for c in calls]
    assert all(pattern("attn_kernel_roofline").search(e) for e in events)
    for metric in ("pam_kernel_roofline", "pam_backward_kernel_roofline"):
        assert not any(pattern(metric).search(e) for e in events)


#: positions of the Keye cell's sequence
KEYE_SEQ = 8192


@pytest.fixture(scope="module")
def keye_block_hlo(v5e_mesh):
    """``keye_lm``'s attention block with the learned sparse attention's
    Mosaic calls live, forward and reverse under ``nn.remat``'s policy, in
    bfloat16 over 1 x 8,192 tokens at the published widths (32 query heads
    to 4 key/value heads of 128, an indexer of 16 heads of 64, top-2,048),
    compiled for the chip: its HLO text."""
    import json
    import os

    from flax import linen as nn

    from distributedpytorch_tpu.models import keye_lm as kl

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "keye_vl2_30b_a3b_lm_stage_ep8.json")) as f:
        cfg = kl.LMConfig.from_dict(json.load(f))
    assert (cfg.q_heads, cfg.kv_heads, cfg.head_dim, cfg.index_heads,
            cfg.index_head_dim, cfg.topk) == (32, 4, 128, 16, 64, 2048)
    one = _one_chip(v5e_mesh)

    class Block(nn.Module):
        @nn.compact
        def __call__(self, u):
            with jax.named_scope(scopes.ATTN):
                return nn.remat(
                    kl.SparseAttention, policy=kl._KEEP_SPARSE_RESIDUALS)(
                        cfg, jnp.bfloat16, name="l00")(u)

    layer = Block()
    u = jax.ShapeDtypeStruct((1, KEYE_SEQ, cfg.hidden_size), jnp.bfloat16,
                             sharding=one)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.hidden_size),
                                             jnp.bfloat16)))["params"])

    def loss(p, v):   # as the step: the loss's value and the counters too
        out, sown = layer.apply({"params": p}, v,
                                mutable=["losses", "counters"])
        return out.astype(jnp.float32).sum() + sum(
            x.sum() for x in jax.tree.leaves(sown["losses"])), sown[
                "counters"]

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(danet_mod, "_on_tpu", lambda: True)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True)).lower(
            params, u).compile().as_text()


def test_sparse_attention_block_compiles_at_the_cells_shape(keye_block_hlo):
    """The Keye block (:func:`keye_block_hlo`): ONE call each of the index
    scores, their reverse, the selection, the forward call, the alignment
    loss's terms (``sparse_probs``) and the fused reverse call (the key set,
    the output, the log-sum-exp and the alignment loss's gradient through
    the scores are kept: the replay recomputes none of the (S, S) arrays);
    no array of (heads, S, S) of any dtype; the scope table puts every call
    under ``attn`` and the part that a metric reads alone."""
    import json
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    seq, hlo = KEYE_SEQ, keye_block_hlo
    names = sorted(ln.split(" = ")[0].strip().lstrip("%").split(".")[0]
                   for ln in _custom_calls(hlo))
    assert set(names) == {
        scopes.INDEXER_SCORES, scopes.INDEXER_SCORES_BWD, scopes.TOPK_KEEP,
        scopes.SPARSE_ATTN, scopes.SPARSE_ATTN_PROBS,
        scopes.SPARSE_ATTN_BWD_FUSED}, names
    assert len(names) == 6, names
    assert not re.search(rf"\[(\d+,)*\d+,{seq},{seq}\]", hlo.replace(
        f"[1,{seq},{seq}]", "[pairs]"))
    table = scopes.scope_table(hlo)
    parts = {}
    for ln in _custom_calls(hlo):
        call = ln.split(" = ")[0].strip().lstrip("%")
        s = table[call]
        assert s.layer == scopes.ATTN and s.path.startswith("attn/l00"), s
        parts.setdefault(call.split(".")[0], set()).update(
            s.path.split("/")[2:-1] or [""])
    assert scopes.ATTN_INDEXER in parts[scopes.INDEXER_SCORES]
    # the scores' reverse call runs in the forward pass, as the indexer's
    # and not the alignment loss's: no two `*_device_ms` count it
    assert parts[scopes.INDEXER_SCORES_BWD] == {scopes.ATTN_INDEXER}
    assert [s.phase for c, s in table.items()
            if c.split(".")[0] == scopes.INDEXER_SCORES_BWD] == ["fwd"]
    assert scopes.ATTN_TOPK_SELECT in parts[scopes.TOPK_KEEP]
    assert scopes.ATTN_INDEX_ALIGN in parts[scopes.SPARSE_ATTN_PROBS]
    # the benchmark's patterns: each roofline metric reads its own calls
    for metric, n in (
            ("sparse_attn_kernel_roofline", 2),
            ("sparse_probs_kernel_roofline", 1),
            ("indexer_scores_kernel_roofline", 1),
            ("indexer_scores_bwd_kernel_roofline", 1),
            ("topk_keep_kernel_roofline", 1),
            ("attn_kernel_roofline", 0), ("pam_kernel_roofline", 0)):
        with open(os.path.join(here, "benchmarks", "metrics",
                               metric + ".json")) as f:
            rx = re.compile(json.load(f)["args"]["event_pattern"])
        events = [ln.split(" = ")[0].strip() + " custom-call"
                  for ln in _custom_calls(hlo)]
        assert sum(bool(rx.search(e)) for e in events) == n, metric


def test_sparse_attention_blocks_alignment_terms_stay_in_the_call(
        keye_block_hlo):
    """The alignment loss's terms come out of the ``sparse_probs`` call
    (``D`` and each row's KL; the target never leaves VMEM): no other
    instruction under ``index_align`` — no fusion, nothing inside one —
    reads or writes a float32 (S, S) array, where the set's log-softmax,
    the KL's terms and ``D`` were XLA passes over them (PR 36's form)."""
    pair = f"f32[1,{KEYE_SEQ},{KEYE_SEQ}]"
    under = [ln for ln in keye_block_hlo.splitlines()
             if f"/{scopes.ATTN_INDEX_ALIGN}/" in ln]
    calls = [ln for ln in under
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert [ln.split(" = ")[0].strip() for ln in calls] == [
        f"%{scopes.SPARSE_ATTN_PROBS}.1"], calls
    passes = [ln[:200] for ln in under if pair in ln and ln not in calls
              and f"get-tuple-element(%{scopes.SPARSE_ATTN_PROBS}" not in ln]
    assert not passes, passes
    # the loss's value and the counters were compiled: something is there
    assert any("reduce" in ln for ln in under), under


def test_block_diffusion_attention_block_compiles_at_the_cells_shape(
        v5e_mesh, monkeypatch):
    """``sdar_lm``'s attention block with the flash kernels live under the
    block-diffusion rule, forward and reverse under ``nn.remat``'s policy,
    in bfloat16 over one sequence of 4,096 tokens — 8,192 positions, clean ‖
    noised — at the published widths (32 query heads to 4 key/value heads of
    128, block length 4): ONE forward call and ONE fused reverse call (the
    output and the log-sum-exp are kept: the replay runs no second forward
    call), under their own names (neither the causal cells' roofline
    pattern nor DANet's matches them, and the new metric's matches both);
    no array of token pairs of any dtype; the scope table puts both calls
    under ``attn``."""
    import json
    import os

    from flax import linen as nn

    from distributedpytorch_tpu.models import sdar_lm as sl

    monkeypatch.setattr(danet_mod, "_on_tpu", lambda: True)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "sdar_30b_a3b_stage_ep8.json")) as f:
        cfg = sl.LMConfig.from_dict(json.load(f))
    assert (cfg.q_heads, cfg.kv_heads, cfg.head_dim, cfg.block_length) == (
        32, 4, 128, 4)
    one = _one_chip(v5e_mesh)
    positions = 2 * 4096

    class Block(nn.Module):
        @nn.compact
        def __call__(self, u):
            with jax.named_scope(scopes.ATTN):
                return nn.remat(
                    sl.BlockDiffusionAttention,
                    policy=sl.KEEP_ATTN_RESIDUALS)(
                        cfg, jnp.bfloat16, name="l00")(u)

    layer = Block()
    u = jax.ShapeDtypeStruct((1, positions, cfg.hidden_size), jnp.bfloat16,
                             sharding=one)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.hidden_size),
                                             jnp.bfloat16)))["params"])

    def loss(p, v):
        return layer.apply({"params": p}, v).astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, u).compile().as_text()
    calls = [ln.split(" = ")[0].strip().lstrip("%")
             for ln in _custom_calls(hlo)]
    assert sorted(c.split(".")[0] for c in calls) == [
        scopes.BLOCKDIFF_ATTN, scopes.BLOCKDIFF_ATTN_BWD_FUSED]
    assert not re.search(rf"\[(\d+,)*{positions},{positions}\]", hlo)
    for ln in _custom_calls(hlo):  # one key/value row a group, not repeated
        operands = ln.split("operand_layout_constraints=", 1)[1]
        assert f"bf16[32,{positions},128]" in operands, ln[:300]
        assert operands.count(f"bf16[4,{positions},128]") == 2, ln[:300]
    table = scopes.scope_table(hlo)
    for call in calls:
        assert table[call].layer == scopes.ATTN
        assert table[call].path.startswith("attn/l00"), table[call]
    with open(os.path.join(here, "benchmarks", "metrics",
                           "blockdiff_attn_kernel_roofline.json")) as f:
        mine = re.compile(json.load(f)["args"]["event_pattern"])
    events = [f"%{c} custom-call" for c in calls]
    assert all(mine.search(e) for e in events)
    for other in ("attn_kernel_roofline", "sparse_attn_kernel_roofline",
                  "pam_kernel_roofline", "pam_backward_kernel_roofline"):
        with open(os.path.join(here, "benchmarks", "metrics",
                               other + ".json")) as f:
            theirs = re.compile(json.load(f)["args"]["event_pattern"])
        assert not any(theirs.search(e) for e in events), other


def test_cca_attention_block_compiles_at_the_cells_shape(v5e_mesh,
                                                         monkeypatch):
    """``zaya_lm``'s compressed convolutional attention block with the causal
    flash kernels live, forward and reverse under ``nn.remat``'s policy, in
    bfloat16 over one sequence of 8,192 tokens at the published widths (8
    query heads to 2 key/value heads of 128 in the latent): ONE forward call
    and ONE fused reverse call, the cell's roofline pattern matching both;
    no array of token pairs; the mixing compiled under ``attn/l00/cca_mix``
    in the forward and the reverse phase."""
    import json
    import os

    from flax import linen as nn

    from distributedpytorch_tpu.models import zaya_lm as zl

    monkeypatch.setattr(danet_mod, "_on_tpu", lambda: True)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "zaya1_8b_stage_ep2.json")) as f:
        cfg = zl.LMConfig.from_dict(json.load(f))
    assert (cfg.q_heads, cfg.kv_heads, cfg.head_dim, cfg.rotary_dim) == (
        8, 2, 128, 64)
    one = _one_chip(v5e_mesh)
    seq = 8192

    class Block(nn.Module):
        @nn.compact
        def __call__(self, u):
            with jax.named_scope(scopes.ATTN):
                return nn.remat(zl.CCAttention,
                                policy=zl._KEEP_FLASH_RESIDUALS)(
                    cfg, jnp.bfloat16, name="l00")(u)

    layer = Block()
    u = jax.ShapeDtypeStruct((1, seq, cfg.hidden_size), jnp.bfloat16,
                             sharding=one)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.hidden_size),
                                             jnp.bfloat16)))["params"])

    def loss(p, v):
        return layer.apply({"params": p}, v).astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, u).compile().as_text()
    calls = [ln.split(" = ")[0].strip().lstrip("%")
             for ln in _custom_calls(hlo)]
    assert sorted(c.split(".")[0] for c in calls) == [
        scopes.CAUSAL_ATTN, scopes.CAUSAL_ATTN_BWD_FUSED]
    assert not re.search(rf"\[(\d+,)*{seq},{seq}\]", hlo)
    for ln in _custom_calls(hlo):  # one key/value row a group, not repeated
        operands = ln.split("operand_layout_constraints=", 1)[1]
        assert f"bf16[8,{seq},128]" in operands, ln[:300]
        assert operands.count(f"bf16[2,{seq},128]") == 2, ln[:300]
    table = scopes.scope_table(hlo)
    mixing = {s.phase for s in table.values()
              if s.path.startswith(f"attn/l00/{scopes.ATTN_CCA_MIX}")}
    assert mixing == {"fwd", "bwd"}
    with open(os.path.join(here, "benchmarks", "metrics",
                           "cca_attn_kernel_roofline.json")) as f:
        mine = re.compile(json.load(f)["args"]["event_pattern"])
    assert all(mine.search(f"%{c} custom-call") for c in calls)


def test_latent_attention_block_compiles_at_the_cells_shape(v5e_mesh,
                                                            monkeypatch):
    """``kanana_lm``'s multi-head latent attention block with the causal
    flash kernels live, forward and reverse under ``nn.remat``'s policy, in
    bfloat16 over one sequence of 8,192 tokens at the published widths (32
    heads, q and k 192 = 128 + 64 wide, v 128, the latent 512): ONE forward
    call and ONE fused reverse call (at 192 channels the reverse pass's
    resident dQ is 16 MiB, the fused schedule's limit: ``_bwd_plan``), each
    reading q and k at 192 and v at 128, the cell's roofline pattern
    matching both; no array of token pairs; the latent path compiled under
    ``attn/l00/mla_latent`` in the forward and the reverse phase."""
    import json
    import os

    from flax import linen as nn

    from distributedpytorch_tpu.models import kanana_lm as kl

    monkeypatch.setattr(danet_mod, "_on_tpu", lambda: True)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "kanana2_30b_a3b_stage_ep8.json")) as f:
        cfg = kl.LMConfig.from_dict(json.load(f))
    assert (cfg.heads, cfg.qk_dim, cfg.v_dim, cfg.kv_rank) == (
        32, 192, 128, 512)
    seq = 8192
    assert pa._bwd_plan(seq, cfg.qk_dim) == (512, True)
    one = _one_chip(v5e_mesh)

    class Block(nn.Module):
        @nn.compact
        def __call__(self, u):
            with jax.named_scope(scopes.ATTN):
                return nn.remat(kl.LatentAttention,
                                policy=kl.KEEP_ATTN_RESIDUALS)(
                    cfg, jnp.bfloat16, name="l00")(u)

    layer = Block()
    u = jax.ShapeDtypeStruct((1, seq, cfg.hidden_size), jnp.bfloat16,
                             sharding=one)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.hidden_size),
                                             jnp.bfloat16)))["params"])

    def loss(p, v):
        return layer.apply({"params": p}, v).astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, u).compile().as_text()
    calls = [ln.split(" = ")[0].strip().lstrip("%")
             for ln in _custom_calls(hlo)]
    assert sorted(c.split(".")[0] for c in calls) == [
        scopes.CAUSAL_ATTN, scopes.CAUSAL_ATTN_BWD_FUSED]
    # the up-projection's output is (8192, 32 x 256): as many channels as
    # tokens, so an array of token pairs is looked for with its heads
    assert not re.search(rf"\[(\d+,)*32,{seq},{seq}\]", hlo)
    assert not re.search(rf"\[(\d+,)*{seq},32,{seq}\]", hlo)
    for ln in _custom_calls(hlo):
        operands = ln.split("operand_layout_constraints=", 1)[1]
        assert operands.count(f"bf16[32,{seq},192]") == 2, ln[:300]
        assert f"bf16[32,{seq},128]" in operands, ln[:300]
    table = scopes.scope_table(hlo)
    latent = {s.phase for s in table.values()
              if s.path.startswith(f"attn/l00/{scopes.MLA_LATENT}")}
    assert latent == {"fwd", "bwd"}
    with open(os.path.join(here, "benchmarks", "metrics",
                           "mla_attn_kernel_roofline.json")) as f:
        mine = re.compile(json.load(f)["args"]["event_pattern"])
    assert all(mine.search(f"%{c} custom-call") for c in calls)
