"""The benchmark's plain reference: dilated ResNet + DANet / DeepLabV3 heads,
their losses and the SGD-momentum update, in straightforward ``jax.numpy``.

It follows the published descriptions (He et al. 2015 bottleneck ResNet with
the stride on the 3x3; Fu et al. arXiv:1809.02983 for the dual-attention head;
Chen et al. arXiv:1706.05587 for ASPP; torchvision's FCN aux head) and imports
nothing from the program under test.  Everything is float32 with
``Precision.HIGHEST`` unless a ``Rounding`` is given, which holds every tensor
the network writes, forward and backward, in a lower type — that is how the
control of ``correct`` is computed.

Parameter trees are plain nested dicts.  Their names are the ones flax gives
the program's modules today (``backbone/BottleneckBlock_3/Conv_1/kernel``), so
that the weights the benchmark makes from the seed fit both sides; the
benchmark's tests compare the two trees leaf by leaf.

Departures from the papers, each because the program does the same and the
two have to compute the same function: BatchNorm keeps the biased batch
variance in its running average (flax), the CAM energies are not scaled, the
heads predict at feature resolution and are resized bilinearly with half-pixel
centres (``jax.image.resize``), and dropout masks are drawn as flax draws them
(the key folded with the SHA-1 of the module path) from the state's key.
"""

from __future__ import annotations

import hashlib
import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HI = lax.Precision.HIGHEST
DEPTHS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3)}
BN_MOMENTUM = 0.9
BN_EPS = 1e-5


# --------------------------------------------------------------- precision

class Rounding:
    """The reference computed in a lower type: every tensor the network
    writes (the operands and the result of each convolution and matrix
    product, each norm's output, each residual sum, the attention maps) is
    rounded to ``dtype`` on the way forward, and its cotangent on the way
    back, as a compute type of that width would hold them; parameters (a
    kernel is rounded as an operand, its gradient is not), norm statistics
    and the loss stay float32.  ``lax.reduce_precision`` does the
    rounding (a convert pair is the compiler's to remove).  8-bit floats
    (1-4-3) get the usual per-tensor scale, amax -> 128."""

    BITS = {"bfloat16": (8, 7), "float16": (5, 10), "float8_e4m3fn": (4, 3)}

    def __init__(self, dtype):
        self.dtype = str(dtype)
        ebits, mbits = self.BITS[self.dtype]
        scaled = ebits < 5

        def cut(x):
            if not scaled:
                return lax.reduce_precision(x, ebits, mbits)
            s = jnp.max(jnp.abs(x)) / 128.0 + 1e-30
            return lax.reduce_precision(x / s, ebits, mbits) * s

        @jax.custom_vjp
        def rnd(x):
            return cut(x)

        rnd.defvjp(lambda x: (cut(x), None), lambda _, g: (cut(g),))

        @jax.custom_vjp
        def weight(w):
            return cut(w)

        weight.defvjp(lambda w: (cut(w), None), lambda _, g: (g,))
        self._rnd, self.weight = rnd, weight

    def __call__(self, x):
        return self._rnd(x)


def _r(q, x):
    return x if q is None else q(x)


# ------------------------------------------------------------ architecture

def stage_plan(output_stride: int):
    """(strides, dilations) of the four stages."""
    return {32: ((1, 2, 2, 2), (1, 1, 1, 1)),
            16: ((1, 2, 2, 1), (1, 1, 1, 2)),
            8: ((1, 2, 1, 1), (1, 1, 2, 4))}[output_stride]


def _conv_spec(k, cin, cout, bias=False, kind="conv"):
    d = {"kernel": ((k, k, cin, cout), kind)}
    if bias:
        d["bias"] = ((cout,), "bias")
    return d


def _bn_spec(c, kind="bn_scale"):
    return {"scale": ((c,), kind), "bias": ((c,), "bn_bias")}


def backbone_spec(cfg: dict) -> dict:
    p = {"Conv_0": _conv_spec(7, cfg["in_channels"], 64),
         "BatchNorm_0": _bn_spec(64)}
    filters, cin, idx = 64, 64, 0
    for n_blocks in DEPTHS[cfg["backbone_depth"]]:
        for i in range(n_blocks):
            cout = filters * 4
            b = {"Conv_0": _conv_spec(1, cin, filters),
                 "BatchNorm_0": _bn_spec(filters),
                 "Conv_1": _conv_spec(3, filters, filters),
                 "BatchNorm_1": _bn_spec(filters),
                 "Conv_2": _conv_spec(1, filters, cout),
                 "BatchNorm_2": _bn_spec(cout, "bn_scale_last")}
            if i == 0:  # the stage's first block changes the width
                b["Conv_3"] = _conv_spec(1, cin, cout)
                b["BatchNorm_3"] = _bn_spec(cout)
            p[f"BottleneckBlock_{idx}"] = b
            idx, cin = idx + 1, cout
        filters *= 2
    return p


def param_spec(cfg: dict) -> dict:
    """Nested dict of ``(shape, kind)`` for every parameter."""
    nclass = cfg["num_classes"]
    spec = {"backbone": backbone_spec(cfg)}
    if cfg["architecture"] == "danet":
        inter = 2048 // 4
        head = {}
        for name in ("pam_in", "pam_out", "cam_in", "cam_out"):
            cin = 2048 if name.endswith("_in") else inter
            head[f"{name}_conv"] = _conv_spec(3, cin, inter)
            head[f"{name}_bn"] = _bn_spec(
                inter, "bn_scale_cam" if name == "cam_in" else "bn_scale")
        head["pam"] = {"query": _conv_spec(1, inter, inter // 8, True, "qk"),
                       "key": _conv_spec(1, inter, inter // 8, True, "qk"),
                       "value": _conv_spec(1, inter, inter, True),
                       "gamma": ((), "gamma")}
        head["cam"] = {"gamma": ((), "gamma")}
        for name in ("fused", "pam", "cam"):
            head[f"{name}_cls"] = _conv_spec(1, inter, nclass, True)
        spec["head"] = head
    elif cfg["architecture"] == "deeplabv3":
        ch = cfg["aspp_channels"]
        aspp = {}
        for name, k, cin in (("b0", 1, 2048), ("b1", 3, 2048), ("b2", 3, 2048),
                             ("b3", 3, 2048), ("pool", 1, 2048),
                             ("project", 1, 5 * ch)):
            aspp[f"{name}_conv"] = _conv_spec(k, cin, ch)
            aspp[f"{name}_bn"] = _bn_spec(ch)
        spec["aspp"] = aspp
        spec["classifier"] = _conv_spec(1, ch, nclass, True)
        if cfg["aux_head"]:
            spec["aux"] = {"Conv_0": _conv_spec(3, 1024, 256),
                           "BatchNorm_0": _bn_spec(256),
                           "Conv_1": _conv_spec(1, 256, nclass, True)}
    else:
        raise ValueError(f"no reference for {cfg['architecture']!r}")
    return spec


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def make_weights(key, cfg: dict):
    """``(params, batch_stats)`` from one key: one normal draw cut into the
    leaves.  Convolutions are LeCun-normal as in the program's own init; the
    scales the program starts at zero (a block's last norm, the attention
    gates) are given a size at which that layer takes part in the loss and in
    the gradients, and the CAM's input norm a small one so that its CxC
    energies stay O(1) and its softmax is not one-hot."""
    spec = param_spec(cfg)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)
    sizes = [math.prod(s) for s, _ in leaves]
    flat = jax.random.normal(key, (sum(sizes),), F32)
    out, off = [], 0
    for (shape, kind), n in zip(leaves, sizes):
        z = flat[off:off + n].reshape(shape)
        off += n
        if kind in ("conv", "qk"):
            fan_in = shape[0] * shape[1] * shape[2]
            out.append(z * (1.0 / math.sqrt(fan_in)))
        elif kind == "bias":
            out.append(0.01 * z)
        elif kind == "bn_scale":
            out.append(1.0 + 0.1 * z)
        elif kind == "bn_scale_last":
            out.append(0.3 + 0.05 * z)
        elif kind == "bn_scale_cam":
            out.append(0.05 * (1.0 + 0.1 * z))
        elif kind == "bn_bias":
            out.append(0.1 * z)
        elif kind == "gamma":
            out.append(0.5 + 0.05 * z)
        else:
            raise ValueError(kind)
    params = jax.tree.unflatten(treedef, out)
    stats = _stats_like(spec)
    return params, stats


def _stats_like(spec):
    out = {}
    for k, v in spec.items():
        if _is_leaf(v):
            continue
        if "scale" in v and _is_leaf(v["scale"]):
            c = v["scale"][0][0]
            out[k] = {"mean": jnp.zeros((c,), F32), "var": jnp.ones((c,), F32)}
        else:
            sub = _stats_like(v)
            if sub:
                out[k] = sub
    return out


# ------------------------------------------------------------------ layers

def conv(x, p, q=None, stride=1, dil=1):
    y = lax.conv_general_dilated(
        _r(q, x), p["kernel"] if q is None else q.weight(p["kernel"]),
        (stride, stride), "SAME",
        rhs_dilation=(dil, dil), dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HI)
    if "bias" in p:
        y = y + p["bias"]
    return _r(q, y)


def batch_norm(x, p, s, q=None):
    """Training-mode BatchNorm over (N, H, W); returns ``(y, new_stats)``."""
    mean = x.mean(axis=(0, 1, 2))
    var = jnp.square(x - mean).mean(axis=(0, 1, 2))
    y = (x - mean) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
    new = {"mean": BN_MOMENTUM * s["mean"] + (1 - BN_MOMENTUM) * mean,
           "var": BN_MOMENTUM * s["var"] + (1 - BN_MOMENTUM) * var}
    return _r(q, y), new


def _cbr(x, p, s, name_c, name_b, new, q, **kw):
    y, new[name_b] = batch_norm(conv(x, p[name_c], q, **kw), p[name_b],
                                s[name_b], q)
    return jax.nn.relu(y)


def bottleneck(p, s, x, stride, dil, q):
    new = {}
    y = _cbr(x, p, s, "Conv_0", "BatchNorm_0", new, q)
    y = _cbr(y, p, s, "Conv_1", "BatchNorm_1", new, q, stride=stride, dil=dil)
    y, new["BatchNorm_2"] = batch_norm(conv(y, p["Conv_2"], q),
                                       p["BatchNorm_2"], s["BatchNorm_2"], q)
    r = x
    if "Conv_3" in p:
        r, new["BatchNorm_3"] = batch_norm(
            conv(x, p["Conv_3"], q, stride=stride), p["BatchNorm_3"],
            s["BatchNorm_3"], q)
    return _r(q, jax.nn.relu(y + r)), new


def _block_run(blocks, p, s, x, dil, q):
    """Identical blocks in a row (stride 1, one dilation, no projection) as
    one ``lax.scan`` over their stacked parameters, each block recomputed in
    the backward pass: the same arithmetic as calling them one after another,
    in a program a quarter of the size."""
    stack = lambda trees: jax.tree.map(lambda *a: jnp.stack(a), *trees)  # noqa: E731
    body = jax.checkpoint(lambda x, ps: bottleneck(*ps, x, 1, dil, q))
    x, new = lax.scan(body, x, (stack([p[b] for b in blocks]),
                                stack([s[b] for b in blocks])))
    return x, {b: jax.tree.map(lambda a, i=i: a[i], new)
               for i, b in enumerate(blocks)}


def backbone(cfg, p, s, x, q, remat):
    """``remat``: the memory-lean form the training step uses (blocks
    recomputed in the backward pass, runs of identical blocks scanned);
    without it every block is written out, which is what the FLOP count
    reads."""
    new = {}
    x = _cbr(x, p, s, "Conv_0", "BatchNorm_0", new, q, stride=2)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    strides, dils = stage_plan(cfg["output_stride"])
    grid = cfg.get("multi_grid")
    feats, idx = {}, 0
    for stage, n_blocks in enumerate(DEPTHS[cfg["backbone_depth"]]):
        plan = []  # (name, stride, dilation) of the stage's blocks
        for i in range(n_blocks):
            dil = dils[stage]
            if stage == 3 and grid:
                dil *= grid[min(i, len(grid) - 1)]
            plan.append((f"BottleneckBlock_{idx}",
                         strides[stage] if i == 0 else 1, dil))
            idx += 1
        i = 0
        while i < n_blocks:
            name, stride, dil = plan[i]
            j = i + 1
            while remat and i > 0 and j < n_blocks and plan[j][2] == dil:
                j += 1
            if j - i > 1:
                x, run = _block_run([b for b, _, _ in plan[i:j]], p, s, x,
                                    dil, q)
                new.update(run)
            else:
                fn = bottleneck
                if remat:
                    fn = jax.checkpoint(bottleneck, static_argnums=(3, 4, 5))
                x, new[name] = fn(p[name], s[name], x, stride, dil, q)
            i = j
        feats[f"c{stage + 1}"] = x
    return feats, new


def flax_key(base, *path):
    """The key flax's ``make_rng`` hands a module at ``path`` on its first
    draw: ``base`` folded with the first 32 bits of the SHA-1 of the path
    and the draw count (1)."""
    m = hashlib.sha1()
    for part in path + (1,):
        if isinstance(part, str):
            m.update(part.encode("utf-8"))
        else:
            m.update(part.to_bytes((part.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(
        base, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def dropout(x, rate, key):
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


def resize(x, size):
    b, _, _, c = x.shape
    return jax.image.resize(x, (b, *size, c), method="bilinear")


def position_attention(p, x, q):
    b, h, w, c = x.shape
    qq = conv(x, p["query"], q).reshape(b, h * w, -1)
    kk = conv(x, p["key"], q).reshape(b, h * w, -1)
    vv = conv(x, p["value"], q).reshape(b, h * w, -1)
    scores = jnp.einsum("bnc,bmc->bnm", _r(q, qq), _r(q, kk), precision=HI)
    attn = jax.nn.softmax(_r(q, scores), axis=-1)
    out = jnp.einsum("bnm,bmc->bnc", _r(q, attn), _r(q, vv), precision=HI)
    return _r(q, p["gamma"] * _r(q, out).reshape(b, h, w, c) + x)


def channel_attention(p, x, q):
    b, h, w, c = x.shape
    t = x.reshape(b, h * w, c)
    energy = _r(q, jnp.einsum("bni,bnj->bij", _r(q, t), _r(q, t),
                              precision=HI))
    energy = energy.max(axis=-1, keepdims=True) - energy
    attn = jax.nn.softmax(energy, axis=-1)
    out = jnp.einsum("bij,bnj->bni", _r(q, attn), _r(q, t), precision=HI)
    return _r(q, p["gamma"] * _r(q, out).reshape(b, h, w, c) + x)


def danet_head(cfg, p, s, c4, size, drop_key, q):
    new = {}
    pa = _cbr(c4, p, s, "pam_in_conv", "pam_in_bn", new, q)
    pa = position_attention(p["pam"], pa, q)
    pa = _cbr(pa, p, s, "pam_out_conv", "pam_out_bn", new, q)
    ca = _cbr(c4, p, s, "cam_in_conv", "cam_in_bn", new, q)
    ca = channel_attention(p["cam"], ca, q)
    ca = _cbr(ca, p, s, "cam_out_conv", "cam_out_bn", new, q)
    outs = []
    for i, (name, y) in enumerate((("fused", pa + ca), ("pam", pa),
                                   ("cam", ca))):
        y = dropout(y, cfg["head_dropout"],
                    flax_key(drop_key, "head", f"Dropout_{i}"))
        outs.append(resize(conv(y, p[f"{name}_cls"], q), size))
    return tuple(outs), new


def aspp(cfg, p, s, x, drop_key, q):
    new = {}
    rates = (6, 12, 18) if cfg["output_stride"] == 16 else (12, 24, 36)
    outs = [_cbr(x, p, s, "b0_conv", "b0_bn", new, q)]
    for i, r in enumerate(rates):
        outs.append(_cbr(x, p, s, f"b{i + 1}_conv", f"b{i + 1}_bn", new, q,
                         dil=r))
    pooled = x.mean(axis=(1, 2), keepdims=True)
    pooled = _cbr(pooled, p, s, "pool_conv", "pool_bn", new, q)
    outs.append(jnp.broadcast_to(pooled, outs[0].shape))
    y = _cbr(jnp.concatenate(outs, axis=-1), p, s, "project_conv",
             "project_bn", new, q)
    return dropout(y, cfg["aspp_dropout"],
                   flax_key(drop_key, "aspp", "Dropout_0")), new


def fcn_head(p, s, x, drop_key, q):
    new = {}
    y = _cbr(x, p, s, "Conv_0", "BatchNorm_0", new, q)
    y = dropout(y, 0.1, flax_key(drop_key, "aux", "Dropout_0"))
    return conv(y, p["Conv_1"], q), new


def forward(cfg, params, stats, x, drop_key, q=None, remat=True):
    """Training-mode forward: ``(outputs at input size, new batch stats)``."""
    size = x.shape[1:3]
    feats, new_bb = backbone(cfg, params["backbone"], stats["backbone"], x,
                             q, remat)
    new = {"backbone": new_bb}
    if cfg["architecture"] == "danet":
        outs, new["head"] = danet_head(cfg, params["head"], stats["head"],
                                       feats["c4"], size, drop_key, q)
        return outs, new
    y, new["aspp"] = aspp(cfg, params["aspp"], stats["aspp"], feats["c4"],
                          drop_key, q)
    outs = [resize(conv(y, params["classifier"], q), size)]
    if cfg["aux_head"]:
        a, new["aux"] = fcn_head(params["aux"], stats["aux"], feats["c3"],
                                 drop_key, q)
        outs.append(resize(a, size))
    return tuple(outs), new


# ------------------------------------------------------------------ losses

def balanced_bce(logits, labels):
    """Class-balanced sigmoid cross-entropy from logits: positives weighted
    by the share of negatives and the reverse, mean over all pixels."""
    per = (jnp.maximum(logits, 0.0) - logits * labels
           + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    w_pos = 1.0 - labels.sum() / labels.size
    return (per * jnp.where(labels > 0.5, w_pos, 1.0 - w_pos)).mean()


def softmax_ce_ignore(logits, labels, ignore=255):
    valid = labels != ignore
    logp = jax.nn.log_softmax(logits, axis=-1)
    gold = jnp.take_along_axis(
        logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    return -(gold * valid).sum() / jnp.maximum(valid.sum(), 1)


def loss_of(cfg, outputs, target):
    if cfg["loss"] == "multi_sigmoid":
        return sum(balanced_bce(o, target[..., None]) for o in outputs)
    if cfg["loss"] == "multi_softmax":
        labels = target.astype(jnp.int32)
        weights = (1.0,) + (cfg["aux_loss_weight"],) * (len(outputs) - 1)
        return sum(w * softmax_ce_ignore(o, labels)
                   for o, w in zip(outputs, weights))
    raise ValueError(cfg["loss"])


# -------------------------------------------------------------------- step

def train_step(cfg, opt, params, stats, trace, rng, batch, q=None,
               remat=True, rows=None):
    """One SGD-momentum step as the program's trainer takes it:
    ``rng`` splits into this step's dropout key and the next state's key.
    ``rows`` (a fault for the tests): use only the first ``rows`` rows."""
    drop_key, next_rng = jax.random.split(rng)
    x, y = batch["concat"], batch["crop_gt"]
    if rows is not None:
        x, y = x[:rows], y[:rows]

    def loss_fn(p):
        outs, new_stats = forward(cfg, p, stats, x, drop_key, q, remat)
        return loss_of(cfg, outs, y), new_stats

    (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params)
    trace = jax.tree.map(lambda g, t: g + opt["momentum"] * t, grads, trace)
    params = jax.tree.map(lambda p, t: p - opt["learning_rate"] * t, params,
                          trace)
    return params, new_stats, trace, next_rng, loss
