"""Ring (sequence-parallel) attention and the pallas flash kernel.

Both must reproduce ``ops.attention.position_attention`` exactly: ring runs
sharded over the 8-device CPU mesh; flash runs in pallas interpreter mode
(the same program Mosaic compiles on TPU), asked for by name on every call
— the kernels never pick it themselves.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedpytorch_tpu.models import DANet, build_model
from distributedpytorch_tpu.ops import (
    blocked_position_attention,
    causal_attention,
    channel_attention,
    pallas_attention,
    position_attention,
)
from distributedpytorch_tpu.parallel import make_mesh, make_ring_attention

flash_position_attention = functools.partial(
    pallas_attention.flash_position_attention, interpret=True)
flash_channel_attention = functools.partial(
    pallas_attention.flash_channel_attention, interpret=True)
flash_causal_attention = functools.partial(
    pallas_attention.flash_causal_attention, interpret=True)


from conftest import assert_grads_close as _assert_grads_close


def qkv(b=2, n=64, ck=16, cv=32, seed=0):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(b, n, ck).astype(np.float32)),
            jnp.asarray(r.randn(b, n, ck).astype(np.float32)),
            jnp.asarray(r.randn(b, n, cv).astype(np.float32)))


class TestRingAttention:
    def test_matches_full_attention(self):
        q, k, v = qkv()
        ring = make_ring_attention(make_mesh())
        out = np.asarray(ring(q, k, v))
        ref = np.asarray(position_attention(q, k, v))
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_scaled_variant(self):
        q, k, v = qkv(seed=1)
        ring = make_ring_attention(make_mesh(), scale=0.125)
        ref = np.asarray(position_attention(q * 0.125, k, v))
        np.testing.assert_allclose(np.asarray(ring(q, k, v)), ref, atol=1e-5)

    def test_local_memory_is_sharded(self):
        # Each device holds N/8 tokens of K/V — check output sharding spec.
        q, k, v = qkv()
        mesh = make_mesh()
        out = make_ring_attention(mesh)(q, k, v)
        assert out.sharding.spec == jax.sharding.PartitionSpec(
            None, "data", None)
        shard = out.addressable_shards[0].data
        assert shard.shape[1] == out.shape[1] // 8

    def test_differentiable(self):
        q, k, v = qkv(seed=2)
        ring = make_ring_attention(make_mesh())

        def loss(q_, k_, v_):
            return (ring(q_, k_, v_) ** 2).sum()

        def ref_loss(q_, k_, v_):
            return (position_attention(q_, k_, v_) ** 2).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)


class TestFlashAttention:
    def test_matches_full_attention_padded(self):
        # N=300 is not a block multiple: exercises the key-mask path.
        q, k, v = qkv(n=300)
        out = flash_position_attention(q, k, v, 128, 128)
        ref = position_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_matches_blocked(self):
        q, k, v = qkv(n=256, seed=3)
        out = flash_position_attention(q, k, v, 64, 64)
        ref = blocked_position_attention(q, k, v, block_size=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_custom_vjp_matches_reference_grad(self):
        q, k, v = qkv(n=128, seed=4)

        def loss(fn):
            return lambda q_, k_, v_: (fn(q_, k_, v_) ** 2).sum()

        g = jax.grad(loss(lambda a, b, c: flash_position_attention(
            a, b, c, 64, 64)), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(position_attention), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-3)

    def test_danet_flash_impl_forward(self, interpreted_kernels):
        m = DANet(nclass=1, backbone_depth=18, output_stride=8,
                  pam_impl="flash", pam_block_size=64)
        x = jnp.zeros((1, 32, 32, 4))
        vs = m.init(jax.random.PRNGKey(0), x, train=False)
        outs = m.apply(vs, x, train=False)
        assert len(outs) == 3 and outs[0].shape == (1, 32, 32, 1)

    def test_interpret_backward_parity_vs_blocked_vjp(self):
        """blocked_position_attention's VJP is the oracle of the Mosaic
        reverse pass (which the custom_vjp ran until PR 27) — pin fwd AND
        grad parity against the blocked form directly, interpret mode,
        scale-aware tolerances.  N=300 is not a block multiple, so the
        padded-key masking is in the differentiated path too."""
        q, k, v = qkv(n=300, seed=5)

        def flash_loss(q_, k_, v_):
            out = flash_position_attention(q_, k_, v_, 128, 128)
            return jnp.sum(out * out * 0.5)

        def blocked_loss(q_, k_, v_):
            out = blocked_position_attention(q_, k_, v_, block_size=128)
            return jnp.sum(out * out * 0.5)

        f_out = flash_position_attention(q, k, v, 128, 128)
        b_out = blocked_position_attention(q, k, v, block_size=128)
        np.testing.assert_allclose(np.asarray(f_out), np.asarray(b_out),
                                   atol=1e-5)
        g_flash = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        g_blocked = jax.grad(blocked_loss, argnums=(0, 1, 2))(q, k, v)
        _assert_grads_close(g_blocked, g_flash)

    def test_scaled_backward_parity_vs_blocked_vjp(self):
        # the reverse pass scales S and dS itself — pin that against the
        # oracle's re-expression (score scaling == scaling q)
        q, k, v = qkv(n=128, seed=6)
        scale = 0.125

        def flash_loss(q_, k_, v_):
            return (flash_position_attention(q_, k_, v_, 64, 64,
                                             scale) ** 2).sum()

        def blocked_loss(q_, k_, v_):
            return (blocked_position_attention(q_ * scale, k_, v_,
                                               block_size=64) ** 2).sum()

        g0 = jax.grad(blocked_loss, argnums=(0, 1, 2))(q, k, v)
        g1 = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        _assert_grads_close(g0, g1)


def _pallas_calls(jaxpr) -> list:
    """Every ``pallas_call`` equation of a jaxpr, sub-jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


def _sq_loss(fn):
    return lambda q_, k_, v_: (fn(q_, k_, v_).astype(jnp.float32) ** 2).sum()


_GRAD = functools.partial(jax.grad, argnums=(0, 1, 2))


class TestFlashBackwardKernels:
    """The reverse pass as Mosaic kernels (interpret mode here): dq, dk, dv
    from the saved output and log-sum-exp, one fused sweep or two."""

    @pytest.fixture()
    def schedule(self, monkeypatch, request):
        """The reverse pass's plan as the shapes give it, or forced to
        3 x 3 tiles of 128 at N=300 in either schedule."""
        forced = getattr(request, "param", None)
        if forced is not None:
            monkeypatch.setattr(pallas_attention, "_bwd_plan",
                                lambda n, ck: (128, forced == "fused"))
        return forced

    @pytest.mark.parametrize("n,ck,cv", [(64, 16, 32), (300, 16, 32),
                                         (64, 8, 64)])
    @pytest.mark.parametrize("scale", [None, 0.125])
    def test_grads_match_full_attention(self, n, ck, cv, scale):
        """N=64 under 256-blocks is one padded tile, forward and reverse;
        N=300 pads 84 keys and queries of the reverse pass's one 384-tile;
        8 / 64 channels keep the flagship's 64 / 512 ratio."""
        q, k, v = qkv(n=n, ck=ck, cv=cv, seed=11)
        g = _GRAD(_sq_loss(lambda a, b, c: flash_position_attention(
            a, b, c, 256, 256, scale)))(q, k, v)
        gr = _GRAD(_sq_loss(lambda a, b, c: position_attention(
            a * (scale or 1.0), b, c)))(q, k, v)
        _assert_grads_close(gr, g)

    @pytest.mark.parametrize("schedule", ["fused", "two_sweeps"],
                             indirect=True)
    @pytest.mark.parametrize("scale", [None, 0.125])
    def test_both_schedules_accumulate_over_tiles(self, schedule, scale):
        q, k, v = qkv(n=300, seed=12)
        jaxpr = jax.make_jaxpr(_GRAD(_sq_loss(
            lambda a, b, c: flash_position_attention(
                a, b, c, 128, 128, scale))))(q, k, v)
        names = [e.params["name"] for e in _pallas_calls(jaxpr.jaxpr)]
        assert names == {"fused": ["pam", "pam_bwd_fused"],
                         "two_sweeps": ["pam", "pam_bwd_dkv", "pam_bwd_dq"]
                         }[schedule]
        g = _GRAD(_sq_loss(lambda a, b, c: flash_position_attention(
            a, b, c, 128, 128, scale)))(q, k, v)
        gr = _GRAD(_sq_loss(lambda a, b, c: blocked_position_attention(
            a * (scale or 1.0), b, c, block_size=128)))(q, k, v)
        _assert_grads_close(gr, g)

    @pytest.mark.parametrize("schedule", [None, "fused", "two_sweeps"],
                             indirect=True)
    def test_bfloat16_inputs(self, schedule):
        """bfloat16 in, bfloat16 out, against autodiff of the einsum form on
        the same bfloat16 inputs.  Both round P and dS to 8 significant bits
        as matmul operands (relative 2^-8) and their results once more, at
        different places (the kernel rounds the unnormalised P, the einsum
        the normalised one): each is within a few 2^-8 of the float32
        gradient, so they differ by at most 4 * 2^-8 of its norm."""
        q, k, v = (x.astype(jnp.bfloat16) for x in qkv(n=300, seed=13))
        g = _GRAD(_sq_loss(lambda a, b, c: flash_position_attention(
            a, b, c, 128, 128)))(q, k, v)
        gr = _GRAD(_sq_loss(position_attention))(q, k, v)
        exact = _GRAD(_sq_loss(position_attention))(
            *(x.astype(jnp.float32) for x in (q, k, v)))
        for got, ref, want in zip(g, gr, exact):
            assert got.dtype == jnp.bfloat16
            norm = float(jnp.linalg.norm(want))
            def off(x):
                return float(jnp.linalg.norm(
                    x.astype(jnp.float32) - want)) / norm
            assert off(got) <= 2 * 2.0 ** -8, off(got)
            assert float(jnp.linalg.norm(
                got.astype(jnp.float32) - ref.astype(jnp.float32))
            ) <= 4 * 2.0 ** -8 * norm

    @pytest.mark.parametrize("n,ck,want", [
        (64, 64, (128, True)), (300, 64, (384, True)),
        (4096, 64, (512, True)), (16384, 64, (512, True)),
        (65536, 64, (512, False)), (16384, 256, (512, False))])
    def test_plan_follows_the_shapes(self, n, ck, want):
        # one padded tile below 512 tokens; the image's float32 dQ stays
        # resident (fused) up to 16 MiB of VMEM, two sweeps beyond
        assert pallas_attention._bwd_plan(n, ck) == want

    def test_forward_only_program_has_one_single_output_call(self):
        # the primal (eval step, serve, Predictor) did not grow an lse
        q, k, v = qkv(n=128)
        fwd = jax.make_jaxpr(
            lambda *a: flash_position_attention(*a, 64, 64))(q, k, v)
        (call,) = _pallas_calls(fwd.jaxpr)
        assert call.params["name"] == "pam" and len(call.outvars) == 1
        # under differentiation the same call also emits the log-sum-exp
        grad = jax.make_jaxpr(_GRAD(_sq_loss(
            lambda *a: flash_position_attention(*a, 64, 64))))(q, k, v)
        pam = [e for e in _pallas_calls(grad.jaxpr)
               if e.params["name"] == "pam"]
        assert len(pam) == 1 and len(pam[0].outvars) == 2
        assert pam[0].outvars[1].aval.dtype == jnp.float32

    def test_backward_on_four_devices_equals_one(self):
        """Through ``_on_local_batch``: the reverse pass's calls shard_map
        onto each device's rows under a context mesh."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from distributedpytorch_tpu.parallel.mesh import traced_on

        mesh = make_mesh(data=4, devices=jax.devices()[:4])
        q, k, v = qkv(b=8, n=128, seed=14)
        grad = _GRAD(_sq_loss(
            lambda a, b, c: flash_position_attention(a, b, c, 64, 64)))
        one = grad(q, k, v)
        sh = NamedSharding(mesh, P("data"))
        sharded = jax.jit(traced_on(mesh, grad), in_shardings=(sh, sh, sh),
                          out_shardings=sh)
        assert "shard_map" in str(jax.make_jaxpr(traced_on(mesh, grad))(
            q, k, v))
        got = sharded(q, k, v)
        assert all(g.sharding.spec == P("data") for g in got)
        # the same tiles on other batch shapes: float32 reassociation only
        _assert_grads_close(one, got)


def heads(s=64, q_heads=4, kv_heads=1, hd=16, b=2, seed=0):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(b, s, q_heads, hd).astype(np.float32)),
            jnp.asarray(r.randn(b, s, kv_heads, hd).astype(np.float32)),
            jnp.asarray(r.randn(b, s, kv_heads, hd).astype(np.float32)))


class TestCausalFlashKernels:
    """Causal grouped-query attention through the same kernels (the token
    model's ``*`` layer): forward and ``jax.grad`` against the einsum form,
    ``ops.attention.causal_attention``, in the pallas interpreter."""

    @pytest.fixture()
    def tiles(self, monkeypatch, request):
        """Tiles of 128 a side, forward and reverse, in the named reverse
        schedule: 64 tokens are one padded tile, 256 are 2 x 2 tiles, 300
        are 3 x 3 with 84 padded keys and queries in the last."""
        schedule = request.param
        monkeypatch.setattr(pallas_attention, "_CAUSAL_TILE", (128, 128))
        monkeypatch.setattr(pallas_attention, "_bwd_plan",
                            lambda n, ck: (128, schedule == "fused"))
        return schedule

    @pytest.mark.parametrize("tiles", ["fused", "two_sweeps"], indirect=True)
    @pytest.mark.parametrize("s", [64, 256, 300])
    @pytest.mark.parametrize("q_heads,kv_heads", [(2, 2), (4, 1)])
    def test_forward_and_grads_match_the_einsum_form(self, tiles, s, q_heads,
                                                     kv_heads):
        q, k, v = heads(s, q_heads, kv_heads, seed=21)
        np.testing.assert_allclose(flash_causal_attention(q, k, v),
                                   causal_attention(q, k, v),
                                   rtol=2e-5, atol=2e-6)
        _assert_grads_close(_GRAD(_sq_loss(causal_attention))(q, k, v),
                            _GRAD(_sq_loss(flash_causal_attention))(q, k, v))

    @pytest.mark.parametrize("tiles", ["fused", "two_sweeps"], indirect=True)
    @pytest.mark.parametrize("q_heads,kv_heads", [(2, 2), (4, 1)])
    def test_bfloat16_inputs(self, tiles, q_heads, kv_heads):
        """As PAM's bfloat16 case: kernel and einsum form round P and dS to
        bfloat16 at different places, each within a few 2^-8 of the float32
        result, so within 4 * 2^-8 of its norm of each other."""
        q, k, v = (x.astype(jnp.bfloat16)
                   for x in heads(300, q_heads, kv_heads, seed=22))
        exact = (causal_attention(*(x.astype(jnp.float32)
                                    for x in (q, k, v))),
                 *_GRAD(_sq_loss(causal_attention))(
                     *(x.astype(jnp.float32) for x in (q, k, v))))
        got = (flash_causal_attention(q, k, v),
               *_GRAD(_sq_loss(flash_causal_attention))(q, k, v))
        ref = (causal_attention(q, k, v),
               *_GRAD(_sq_loss(causal_attention))(q, k, v))
        for g, r, want in zip(got, ref, exact):
            assert g.dtype == jnp.bfloat16 and g.shape == want.shape
            norm = float(jnp.linalg.norm(want))
            assert float(jnp.linalg.norm(
                g.astype(jnp.float32) - want)) <= 2 * 2.0 ** -8 * norm
            assert float(jnp.linalg.norm(
                g.astype(jnp.float32) - r.astype(jnp.float32))
            ) <= 4 * 2.0 ** -8 * norm

    @pytest.mark.parametrize("tiles", ["fused", "two_sweeps"], indirect=True)
    def test_calls_are_named_and_read_one_key_value_head(self, tiles):
        q, k, v = heads(300, 4, 1)
        jaxpr = jax.make_jaxpr(_GRAD(_sq_loss(flash_causal_attention)))(
            q, k, v)
        calls = _pallas_calls(jaxpr.jaxpr)
        assert [e.params["name"] for e in calls] == {
            "fused": ["causal_attn", "causal_attn_bwd_fused"],
            "two_sweeps": ["causal_attn", "causal_attn_bwd_dkv",
                           "causal_attn_bwd_dq"]}[tiles]
        for e in calls:  # q by query head, k and v by key/value head
            rows = [x.aval.shape[0] for x in e.invars[:3]]
            assert rows == [2 * 4, 2 * 1, 2 * 1], (e.params["name"], rows)
            assert not any(x.aval.shape[-2:] == (384, 384)
                           for x in e.invars + e.outvars)

    @pytest.mark.parametrize("tiles", ["fused", "two_sweeps"], indirect=True)
    def test_tiles_above_the_diagonal_are_not_computed(self, tiles):
        """A NaN among the later keys and values poisons a masked product
        (0 x NaN) but not a tile that never runs: the queries of the first
        block, and their own gradient, stay what they are without it."""
        q, k, v = heads(256, 4, 1, seed=23)
        late = jnp.arange(256)[None, :, None, None] >= 128
        k_bad, v_bad = (jnp.where(late, jnp.nan, x) for x in (k, v))
        np.testing.assert_array_equal(
            flash_causal_attention(q, k_bad, v_bad)[:, :128],
            flash_causal_attention(q, k, v)[:, :128])

        def first_block_loss(q_, k_, v_):
            out = flash_causal_attention(q_, k_, v_)[:, :128]
            return (out.astype(jnp.float32) ** 2).sum()

        # (dK, dV of the first keys do hear from the later queries, whose
        # own rows the NaN keys have rightly poisoned)
        dq_bad = jax.grad(first_block_loss)(q, k_bad, v_bad)
        dq = jax.grad(first_block_loss)(q, k, v)
        np.testing.assert_array_equal(dq_bad[:, :128], dq[:, :128])

    def test_uneven_groups_are_refused(self):
        q, k, v = heads(64, 3, 2)
        with pytest.raises(ValueError, match="3 query heads"):
            flash_causal_attention(q, k, v)


class TestFlashChannelAttention:
    """The fused gram-branch kernel: parity with the XLA reference in
    interpret mode, forward and backward, plus the model wiring."""

    def x(self, b=2, n=100, c=32, seed=7):
        r = np.random.RandomState(seed)
        return jnp.asarray(r.randn(b, n, c).astype(np.float32))

    def test_matches_reference_padded(self):
        # N=100 is not a block multiple: zero-padded rows contribute
        # zero to the gram and padded outputs are sliced off
        x = self.x()
        out = flash_channel_attention(x, 64)
        ref = channel_attention(x)
        # 5e-5: the kernel accumulates the gram blockwise (f32 partial
        # sums) where the einsum reduces in one pass — reassociation
        # noise only; a masking/softmax bug moves outputs by ~1e-1
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-5)

    def test_matches_reference_exact_blocks(self):
        x = self.x(n=128, seed=8)
        out = flash_channel_attention(x, 64)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(channel_attention(x)),
                                   atol=5e-5)

    def test_custom_vjp_matches_reference_grad(self):
        x = self.x(n=96, seed=9)

        def loss(fn):
            return lambda x_: (fn(x_) ** 2).sum()

        g = jax.grad(loss(lambda v: flash_channel_attention(v, 32)))(x)
        gr = jax.grad(loss(channel_attention))(x)
        _assert_grads_close((gr,), (g,))

    def test_bf16_input_keeps_dtype(self):
        x = self.x().astype(jnp.bfloat16)
        out = flash_channel_attention(x, 64)
        assert out.dtype == jnp.bfloat16
        ref = channel_attention(x)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=3e-2)

    def test_danet_cam_flash_matches_einsum(self, interpreted_kernels):
        x = jnp.asarray(np.random.RandomState(1).normal(
            size=(1, 32, 32, 4)), jnp.float32)
        m_ein = DANet(nclass=1, backbone_depth=18, output_stride=8)
        m_flash = DANet(nclass=1, backbone_depth=18, output_stride=8,
                        cam_impl="flash")
        # param trees identical (both attention impls are param-free)
        vs = m_ein.init(
            {"params": jax.random.key(0), "dropout": jax.random.key(1)},
            x, train=False)
        a = m_ein.apply(vs, x, train=False)
        b = m_flash.apply(vs, x, train=False)
        for oa, ob in zip(a, b):
            np.testing.assert_allclose(np.asarray(oa), np.asarray(ob),
                                       rtol=1e-4, atol=1e-4)

    def test_unknown_impl_raises(self):
        m = DANet(nclass=1, backbone_depth=18, output_stride=8,
                  cam_impl="cuda")
        x = jnp.zeros((1, 32, 32, 4))
        with pytest.raises(ValueError, match="channel-attention impl"):
            m.init({"params": jax.random.key(0),
                    "dropout": jax.random.key(1)}, x, train=False)


class TestKernelsUnderMesh:
    """GSPMD cannot partition a Mosaic call: under a multi-device jit the
    kernels shard_map themselves onto the local batch shard (the mesh
    comes from parallel.mesh.traced_on — what make_train_step /
    make_eval_step wrap their programs in)."""

    def test_runs_on_local_batch_shard(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from distributedpytorch_tpu.parallel.mesh import traced_on

        mesh = make_mesh()
        seen = []

        def local_rows(x):
            seen.append(x.shape[0])
            return x

        def f(q, k, v):
            pallas_attention._on_local_batch(local_rows, q)
            return (flash_position_attention(q, k, v, 64, 64),
                    flash_channel_attention(v, 64))

        q, k, v = qkv(b=8, n=128)
        sh = NamedSharding(mesh, P("data"))
        pam, cam = jax.jit(traced_on(mesh, f), in_shardings=(sh, sh, sh),
                           out_shardings=sh)(q, k, v)
        assert seen == [1]  # 8 rows over 8 devices
        np.testing.assert_allclose(
            np.asarray(pam), np.asarray(position_attention(q, k, v)),
            atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(cam), np.asarray(channel_attention(v)), atol=1e-4)

    def test_no_mesh_calls_kernel_directly(self):
        # single-device programs (predict/serve) trace with no context
        # mesh and must not grow a shard_map
        q, k, v = qkv(n=64)
        jaxpr = str(jax.make_jaxpr(
            lambda *a: flash_position_attention(*a, 64, 64))(q, k, v))
        assert "shard_map" not in jaxpr and "pallas_call" in jaxpr


class TestAttentionImplKnob:
    """model.attention_impl — one knob, both branches (build_model)."""

    def test_auto_resolves_flash_on_tpu_bf16(self, monkeypatch):
        # 'auto' promotes the Pallas kernels only for the bf16-TPU hot
        # path (train.precision couples the model dtype) — pinned by
        # spying the kernel entry points the module imports at call time
        from distributedpytorch_tpu.models import danet as danet_mod
        from distributedpytorch_tpu.ops import pallas_attention as pa

        monkeypatch.setattr(danet_mod, "_on_tpu", lambda: True)
        called = set()
        real_pam = pa.flash_position_attention
        real_cam = pa.flash_channel_attention
        monkeypatch.setattr(
            pa, "flash_position_attention",
            lambda *a: called.add("pam") or real_pam(*a, interpret=True))
        monkeypatch.setattr(
            pa, "flash_channel_attention",
            lambda *a: called.add("cam") or real_cam(*a, interpret=True))
        x = jnp.asarray(np.random.RandomState(2).normal(
            size=(1, 32, 32, 4)), jnp.float32)
        m_auto = build_model("danet", nclass=1, backbone="resnet18",
                             output_stride=8, dtype=jnp.bfloat16)
        assert m_auto.pam_impl == "auto" and m_auto.cam_impl == "auto"
        vs = m_auto.init(
            {"params": jax.random.key(0), "dropout": jax.random.key(1)},
            x, train=False)
        out = m_auto.apply(vs, x, train=False)
        assert called == {"pam", "cam"}
        for o in out:
            assert np.isfinite(np.asarray(o, np.float32)).all()

    def test_auto_stays_xla_for_f32_on_tpu(self, monkeypatch):
        # the f32 crossover sweep verdict stands even on TPU: einsum is
        # faster at every compilable token count, so an f32 'auto' model
        # traces the reference einsum program bitwise
        from distributedpytorch_tpu.models import danet as danet_mod

        monkeypatch.setattr(danet_mod, "_on_tpu", lambda: True)
        x = jnp.asarray(np.random.RandomState(2).normal(
            size=(1, 32, 32, 4)), jnp.float32)
        m_auto = build_model("danet", nclass=1, backbone="resnet18",
                             output_stride=8)  # f32 default dtype
        m_ref = build_model("danet", nclass=1, backbone="resnet18",
                            output_stride=8, attention_impl="xla")
        assert m_ref.pam_impl == "einsum" and m_ref.cam_impl == "einsum"
        vs = m_ref.init(
            {"params": jax.random.key(0), "dropout": jax.random.key(1)},
            x, train=False)
        a = m_auto.apply(vs, x, train=False)
        b = m_ref.apply(vs, x, train=False)
        for oa, ob in zip(a, b):
            np.testing.assert_array_equal(np.asarray(oa), np.asarray(ob))

    def test_auto_is_xla_off_tpu(self):
        # on the CPU mesh 'auto' lowers to the einsum forms: the traced
        # program is bitwise the reference path
        x = jnp.asarray(np.random.RandomState(3).normal(
            size=(1, 16, 16, 4)), jnp.float32)
        m_auto = build_model("danet", nclass=1, backbone="resnet18",
                             output_stride=8)
        m_ein = build_model("danet", nclass=1, backbone="resnet18",
                            output_stride=8, attention_impl="xla")
        vs = m_ein.init(
            {"params": jax.random.key(0), "dropout": jax.random.key(1)},
            x, train=False)
        a = m_auto.apply(vs, x, train=False)
        b = m_ein.apply(vs, x, train=False)
        for oa, ob in zip(a, b):
            np.testing.assert_array_equal(np.asarray(oa), np.asarray(ob))

    def test_flash_forces_pallas_everywhere(self):
        m = build_model("danet", nclass=1, backbone="resnet18",
                        output_stride=8, attention_impl="flash")
        assert m.pam_impl == "flash" and m.cam_impl == "flash"

    def test_pam_impl_overrides_position_branch_only(self):
        m = build_model("danet", nclass=1, backbone="resnet18",
                        output_stride=8, attention_impl="flash",
                        pam_impl="einsum")
        assert m.pam_impl == "einsum" and m.cam_impl == "flash"

    def test_unknown_attention_impl_raises(self):
        with pytest.raises(ValueError, match="attention_impl"):
            build_model("danet", nclass=1, backbone="resnet18",
                        attention_impl="cudnn")

    def test_danet_only(self):
        with pytest.raises(ValueError, match="DANet-only"):
            build_model("deeplabv3", nclass=21, backbone="resnet50",
                        attention_impl="flash")
        # the legacy spelled-out default on old configs stays accepted
        build_model("deeplabv3", nclass=21, backbone="resnet50",
                    pam_impl="einsum")


class TestRingPAMInModel:
    """impl='ring' in the DANet head: sequence parallelism live in the
    flagship model — tokens sharded over the mesh's model axis."""

    def test_ring_pam_matches_einsum(self):
        from distributedpytorch_tpu.models import DANet
        from distributedpytorch_tpu.parallel import make_mesh

        mesh = make_mesh(data=2, model=4)
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(2, 32, 32, 4)), jnp.float32)  # tokens: 16 = 4 ring hops x 4
        m_ref = DANet(nclass=1, backbone_depth=18, output_stride=8)
        m_ring = DANet(nclass=1, backbone_depth=18, output_stride=8,
                       pam_impl="ring", pam_sp_mesh=mesh)
        variables = m_ref.init(
            {"params": jax.random.key(0), "dropout": jax.random.key(1)},
            x, train=False)
        a = m_ref.apply(variables, x, train=False)
        with mesh:
            b = m_ring.apply(variables, x, train=False)
        for oa, ob in zip(a, b):
            np.testing.assert_allclose(np.asarray(oa), np.asarray(ob),
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.slow  # tier-1 budget (PR 20): sharded training loop
    # (~9s); fast gate: test_ring_pam_matches_einsum (numerics parity)
    def test_ring_pam_trains_under_sharded_step(self):
        import optax

        from distributedpytorch_tpu.models import DANet
        from distributedpytorch_tpu.parallel import (
            create_train_state,
            make_mesh,
            make_train_step,
            shard_batch,
        )

        mesh = make_mesh(data=2, model=4)
        m = DANet(nclass=1, backbone_depth=18, output_stride=8,
                  pam_impl="ring", pam_sp_mesh=mesh)
        tx = optax.sgd(1e-3, momentum=0.9)
        r = np.random.RandomState(0)
        with mesh:
            state = create_train_state(jax.random.PRNGKey(0), m, tx,
                                       (1, 32, 32, 4), mesh=mesh)
            step = make_train_step(m, tx, mesh=mesh)
            batch = shard_batch(mesh, {
                "concat": r.uniform(0, 255, (4, 32, 32, 4)
                                    ).astype(np.float32),
                "crop_gt": (r.uniform(size=(4, 32, 32)) > 0.7
                            ).astype(np.float32),
            })
            state, loss = step(state, batch)
            jax.block_until_ready(loss)
        assert np.isfinite(float(loss))

    def test_ring_without_mesh_raises(self):
        from distributedpytorch_tpu.models import DANet

        m = DANet(nclass=1, backbone_depth=18, output_stride=8,
                  pam_impl="ring")
        x = jnp.zeros((1, 32, 32, 4))
        with pytest.raises(ValueError, match="sp_mesh"):
            m.init({"params": jax.random.key(0),
                    "dropout": jax.random.key(1)}, x, train=False)

    def test_ring_indivisible_tokens_raises(self):
        from distributedpytorch_tpu.models import DANet
        from distributedpytorch_tpu.parallel import make_mesh

        mesh = make_mesh(data=2, model=4)  # 24x24 -> 9 tokens, 9 % 4 != 0
        m = DANet(nclass=1, backbone_depth=18, output_stride=8,
                  pam_impl="ring", pam_sp_mesh=mesh)
        x = jnp.zeros((1, 24, 24, 4))
        with pytest.raises(ValueError, match="divisible"):
            m.init({"params": jax.random.key(0),
                    "dropout": jax.random.key(1)}, x, train=False)

    def test_ring_pam_composes_with_tensor_parallel(self):
        """SP (ring PAM over `model`) + TP (params sharded over `model`) in
        the same compiled step — the manual shard_map region must coexist
        with GSPMD-partitioned convolutions."""
        import optax

        from distributedpytorch_tpu.models import DANet
        from distributedpytorch_tpu.parallel import (
            create_train_state,
            make_mesh,
            make_train_step,
            shard_batch,
            state_shardings,
        )

        mesh = make_mesh(data=2, model=4)
        m = DANet(nclass=1, backbone_depth=18, output_stride=8,
                  pam_impl="ring", pam_sp_mesh=mesh)
        tx = optax.sgd(1e-3, momentum=0.9)
        r = np.random.RandomState(0)
        with mesh:
            state = create_train_state(jax.random.PRNGKey(0), m, tx,
                                       (1, 32, 32, 4), mesh=mesh,
                                       shard_params=True)
            step = make_train_step(
                m, tx, mesh=mesh, state_shardings=state_shardings(state))
            batch = shard_batch(mesh, {
                "concat": r.uniform(0, 255, (4, 32, 32, 4)
                                    ).astype(np.float32),
                "crop_gt": (r.uniform(size=(4, 32, 32)) > 0.7
                            ).astype(np.float32),
            })
            state, loss = step(state, batch)
            jax.block_until_ready(loss)
        assert np.isfinite(float(loss))
