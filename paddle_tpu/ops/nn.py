"""NN ops: conv/pool/norm/dropout/softmax/losses.

Reference: paddle/fluid/operators/{conv_op.cc,conv_cudnn_op.cu.cc,
pool_op.cc,batch_norm_op.cc,layer_norm_op.cc,dropout_op.cc,softmax_op.cc,
cross_entropy_op.cc,softmax_with_cross_entropy_op.cc,...}. The cuDNN
dispatch (`use_cudnn` attr) has no TPU meaning: XLA lowers conv/matmul onto
the MXU directly, so the attr is accepted and ignored.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op


# ---------------------------------------------------------------------------
# Convolutions (NCHW like the reference; lax conv handles layout for TPU)
# ---------------------------------------------------------------------------


def _conv_padding(attrs, spatial_rank, strides, x_spatial, k_spatial, dilations):
    algo = attrs.get("padding_algorithm", "EXPLICIT")
    if algo == "SAME":
        return "SAME"
    if algo == "VALID":
        return "VALID"
    pads = [int(p) for p in attrs.get("paddings", [0] * spatial_rank)]
    if len(pads) == spatial_rank:
        return [(p, p) for p in pads]
    # [before0, after0, before1, after1, ...]
    return [(pads[2 * i], pads[2 * i + 1]) for i in range(spatial_rank)]


def _conv_nd(x, w, attrs, nd, feature_group_count=None, f32_accum=True):
    strides = tuple(int(s) for s in attrs.get("strides", [1] * nd))
    dilations = tuple(int(d) for d in attrs.get("dilations", [1] * nd))
    groups = int(attrs.get("groups", 1)) if feature_group_count is None else feature_group_count
    padding = _conv_padding(attrs, nd, strides, x.shape[2:], w.shape[2:], dilations)
    dn_str = ("NCHW", "OIHW", "NCHW") if nd == 2 else ("NCDHW", "OIDHW", "NCDHW")
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape, dn_str)
    # f32_accum (inference only): explicit f32 accumulation for bf16
    # convs. The TRAINING path must not request it — jax's (0.9.0) conv
    # transpose rule feeds the f32-typed cotangent back into a conv
    # against the bf16 filter and rejects the dtype mix, so the
    # differentiable path accumulates at the input width (the TPU MXU
    # accumulates bf16 partials in f32 internally regardless).
    accum = jnp.float32 if f32_accum and x.dtype == jnp.bfloat16 else None
    return jax.lax.conv_general_dilated(
        x, w, window_strides=strides, padding=padding,
        rhs_dilation=dilations, dimension_numbers=dn,
        feature_group_count=groups,
        preferred_element_type=accum,
    ).astype(x.dtype)


@register_op("conv2d", nondiff_inputs=())
def conv2d(ins, attrs, ctx):
    x, w = ins["Input"][0], ins["Filter"][0]
    out = _conv_nd(x, w, attrs, 2, f32_accum=ctx.is_test)
    if ins.get("Bias") and ins["Bias"][0] is not None:
        out = out + ins["Bias"][0].reshape(1, -1, 1, 1)
    return {"Output": out}


@register_op("depthwise_conv2d")
def depthwise_conv2d(ins, attrs, ctx):
    x, w = ins["Input"][0], ins["Filter"][0]
    # reference: groups == in_channels; lax expects OIHW with I = C/groups = 1
    out = _conv_nd(x, w, attrs, 2, feature_group_count=x.shape[1],
                   f32_accum=ctx.is_test)
    return {"Output": out}


@register_op("conv3d")
def conv3d(ins, attrs, ctx):
    x, w = ins["Input"][0], ins["Filter"][0]
    return {"Output": _conv_nd(x, w, attrs, 3, f32_accum=ctx.is_test)}


@register_op("conv2d_transpose")
def conv2d_transpose(ins, attrs, ctx):
    x, w = ins["Input"][0], ins["Filter"][0]  # w: [C_in, C_out/groups, H, W]
    strides = tuple(int(s) for s in attrs.get("strides", [1, 1]))
    dilations = tuple(int(d) for d in attrs.get("dilations", [1, 1]))
    pads = attrs.get("paddings", [0, 0])
    if len(pads) == 2:
        pad_pairs = [(int(p), int(p)) for p in pads]
    else:
        pad_pairs = [(int(pads[0]), int(pads[1])),
                     (int(pads[2]), int(pads[3]))]
    # jax's conv_transpose applies `padding` to the underlying dilated
    # conv; the transpose of a conv padded by p needs (k-1)*d - p so the
    # output is (in-1)*s - 2p + (k-1)*d + 1 (conv_transpose_op.cc shape)
    padding = [((w.shape[2 + i] - 1) * dilations[i] - lo,
                (w.shape[2 + i] - 1) * dilations[i] - hi)
               for i, (lo, hi) in enumerate(pad_pairs)]
    # kernel layout is [C_in, C_out, H, W]; with transpose_kernel=True
    # conv_transpose swaps the I/O labels, so axis 0 must be labeled O for
    # the effective input-feature axis to be C_in (C_in != C_out broke
    # under "IOHW")
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))
    out = jax.lax.conv_transpose(
        x, w, strides=strides, padding=padding,
        rhs_dilation=dilations, dimension_numbers=dn, transpose_kernel=True)
    return {"Output": out}


# ---------------------------------------------------------------------------
# Pooling (reference: operators/pool_op.cc; math/pooling.{cc,cu})
# ---------------------------------------------------------------------------


def _pool2d(x, attrs):
    ptype = attrs.get("pooling_type", "max")
    ksize = [int(k) for k in attrs.get("ksize", [2, 2])]
    strides = [int(s) for s in attrs.get("strides", ksize)]
    pads = [int(p) for p in attrs.get("paddings", [0, 0])]
    if attrs.get("global_pooling", False) or attrs.get("adaptive", False) and all(
            k == 1 for k in ksize):
        if ptype == "max":
            return jnp.max(x, axis=(2, 3), keepdims=True)
        return jnp.mean(x, axis=(2, 3), keepdims=True)
    if attrs.get("adaptive", False):
        n, c, h, w = x.shape
        oh, ow = ksize
        assert h % oh == 0 and w % ow == 0, "adaptive pool needs divisible dims"
        xr = x.reshape(n, c, oh, h // oh, ow, w // ow)
        return jnp.max(xr, axis=(3, 5)) if ptype == "max" else jnp.mean(xr, axis=(3, 5))

    window = (1, 1, ksize[0], ksize[1])
    strides_ = (1, 1, strides[0], strides[1])
    if len(pads) == 2:
        padding = [(0, 0), (0, 0), (pads[0], pads[0]), (pads[1], pads[1])]
    else:
        padding = [(0, 0), (0, 0), (pads[0], pads[1]), (pads[2], pads[3])]
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return jax.lax.reduce_window(x, init, jax.lax.max, window, strides_, padding)
    s = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides_, padding)
    if attrs.get("exclusive", True) and any(p != (0, 0) for p in padding):
        ones = jnp.ones_like(x)
        counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides_, padding)
        return s / counts
    return s / (ksize[0] * ksize[1])


@register_op("pool2d")
def pool2d(ins, attrs, ctx):
    return {"Out": _pool2d(ins["X"][0], attrs)}


@register_op("pool3d")
def pool3d(ins, attrs, ctx):
    x = ins["X"][0]
    ptype = attrs.get("pooling_type", "max")
    if attrs.get("global_pooling", False):
        fn = jnp.max if ptype == "max" else jnp.mean
        return {"Out": fn(x, axis=(2, 3, 4), keepdims=True)}
    ksize = [int(k) for k in attrs.get("ksize", [2, 2, 2])]
    strides = [int(s) for s in attrs.get("strides", ksize)]
    pads = [int(p) for p in attrs.get("paddings", [0, 0, 0])]
    window = (1, 1) + tuple(ksize)
    strides_ = (1, 1) + tuple(strides)
    padding = [(0, 0), (0, 0)] + [(p, p) for p in pads]
    if ptype == "max":
        return {"Out": jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window, strides_, padding)}
    s = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides_, padding)
    return {"Out": s / float(np.prod(ksize))}


def _bilinear_sample_chw(x, ys, xs):
    """Bilinear sample x [C,H,W] at float coords (ys, xs) of any shape;
    out-of-range corners contribute 0 (the reference deformable kernels'
    zero-padding semantics). Returns [C, *ys.shape]."""
    h, w = x.shape[1:]
    y0 = jnp.floor(ys)
    x0 = jnp.floor(xs)
    wy = (ys - y0).astype(x.dtype)
    wx = (xs - x0).astype(x.dtype)

    def gather(yy, xx):
        inb = (yy >= 0) & (yy <= h - 1) & (xx >= 0) & (xx <= w - 1)
        yc = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
        xc = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
        return x[:, yc, xc] * inb.astype(x.dtype)

    return (gather(y0, x0) * (1 - wy) * (1 - wx)
            + gather(y0, x0 + 1) * (1 - wy) * wx
            + gather(y0 + 1, x0) * wy * (1 - wx)
            + gather(y0 + 1, x0 + 1) * wy * wx)


def _deformable_conv(ins, attrs, modulated):
    """reference: deformable_conv_op.h (v2, modulated) /
    deformable_conv_v1_op.h — y(p) = sum_k w_k * x(p + p_k + dp_k) * dm_k.
    TPU-native: bilinear gather of the K sampled taps into an im2col
    column tensor, then one grouped einsum on the MXU (replaces the
    reference's ModulatedDeformableIm2col + GEMM per image)."""
    x = ins["Input"][0]                       # [N, C, H, W]
    off = ins["Offset"][0]                    # [N, dg*K*2, OH, OW]
    w = ins["Filter"][0]                      # [Cout, C/groups, kh, kw]
    strides = [int(s) for s in attrs.get("strides", [1, 1])]
    pads = [int(p) for p in attrs.get("paddings", [0, 0])]
    dils = [int(d) for d in attrs.get("dilations", [1, 1])]
    groups = int(attrs.get("groups", 1))
    dg = int(attrs.get("deformable_groups", 1))
    n, c, h_in, w_in = x.shape
    cout, cg, kh, kw = w.shape
    K = kh * kw
    oh, ow = off.shape[2], off.shape[3]
    cpg = c // dg

    # base sampling grid: h = oh*stride - pad + ki*dilation (+ offset)
    ki = (jnp.arange(K) // kw).astype(x.dtype)
    kj = (jnp.arange(K) % kw).astype(x.dtype)
    base_y = (jnp.arange(oh, dtype=x.dtype) * strides[0] - pads[0])
    base_x = (jnp.arange(ow, dtype=x.dtype) * strides[1] - pads[1])
    grid_y = base_y[None, :, None] + ki[:, None, None] * dils[0]  # [K,OH,1]
    grid_x = base_x[None, None, :] + kj[:, None, None] * dils[1]  # [K,1,OW]

    off = off.reshape(n, dg, K, 2, oh, ow)
    if modulated:
        mask = ins["Mask"][0].reshape(n, dg, K, oh, ow)
    else:
        mask = None

    def per_image(xi, offi, maski):
        def per_group(xg, og, mg):
            ys = grid_y + og[:, 0]            # [K, OH, OW]
            xs = grid_x + og[:, 1]
            v = _bilinear_sample_chw(xg, ys, xs)   # [cpg, K, OH, OW]
            return v if mg is None else v * mg[None].astype(v.dtype)
        xg = xi.reshape(dg, cpg, h_in, w_in)
        if maski is None:
            cols = jax.vmap(lambda a, b: per_group(a, b, None))(xg, offi)
        else:
            cols = jax.vmap(per_group)(xg, offi, maski)
        return cols.reshape(c, K, oh, ow)

    if mask is None:
        cols = jax.vmap(lambda a, b: per_image(a, b, None))(x, off)
    else:
        cols = jax.vmap(per_image)(x, off, mask)

    cols_g = cols.reshape(n, groups, cg, K, oh, ow)
    w_g = w.reshape(groups, cout // groups, cg, K).astype(cols.dtype)
    out = jnp.einsum("ngckhw,gock->ngohw", cols_g, w_g)
    return {"Output": out.reshape(n, cout, oh, ow)}


@register_op("deformable_conv")
def deformable_conv(ins, attrs, ctx):
    return _deformable_conv(ins, attrs, modulated=True)


@register_op("deformable_conv_v1")
def deformable_conv_v1(ins, attrs, ctx):
    return _deformable_conv(ins, attrs, modulated=False)


def _max_pool_with_index(x, attrs, nd):
    """Shared kernel for max_pool{2,3}d_with_index (reference:
    pool_with_index_op.cc, math/pooling.cc MaxPool*WithIdxFunctor).
    Mask = row-major flat index of the argmax within each channel's input
    volume; argmax keeps the FIRST maximum in scan order, like the
    reference's strict `<` comparison."""
    spatial = x.shape[2:]
    ksize = [int(k) for k in attrs.get("ksize", [2] * nd)]
    if attrs.get("global_pooling", False):
        ksize = list(spatial)
    strides = [int(s) for s in attrs.get("strides", ksize)]
    pads = [int(p) for p in attrs.get("paddings", [0] * nd)]
    if attrs.get("global_pooling", False):
        pads = [0] * nd
    if attrs.get("adaptive", False):
        # divisible adaptive bins (same convention as _pool2d)
        out_sz = ksize
        assert all(s % o == 0 for s, o in zip(spatial, out_sz)), \
            "adaptive pool needs divisible dims"
        ksize = [s // o for s, o in zip(spatial, out_sz)]
        strides = ksize
        pads = [0] * nd

    neg = jnp.finfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.iinfo(x.dtype).min
    xp = jnp.pad(x, [(0, 0), (0, 0)] + [(p, p) for p in pads],
                 constant_values=neg)
    # patches: [N, C*prod(k), *out_spatial], feature dim ordered (C, k...)
    patches = jax.lax.conv_general_dilated_patches(
        xp, tuple(ksize), tuple(strides), "VALID")
    n, c = x.shape[:2]
    out_sp = patches.shape[2:]
    K = int(np.prod(ksize))
    patches = patches.reshape((n, c, K) + out_sp)
    k_local = jnp.argmax(patches, axis=2)                    # [N, C, *out]
    out = jnp.take_along_axis(patches, k_local[:, :, None], axis=2)[:, :, 0]

    # local k -> global row-major input index (padding never wins: its
    # value is dtype-min and every window overlaps >=1 real cell)
    idx = jnp.zeros(k_local.shape, jnp.int32)
    rem = k_local
    for d in range(nd):
        tail = int(np.prod(ksize[d + 1:]))
        kd = rem // tail
        rem = rem % tail
        coord = jnp.arange(out_sp[d]) * strides[d] - pads[d]
        shape = [1] * (2 + nd)
        shape[2 + d] = out_sp[d]
        g = coord.reshape(shape) + kd
        idx = idx * spatial[d] + g.astype(jnp.int32)
    return out, idx


@register_op("max_pool2d_with_index", intermediate_outputs=())
def max_pool2d_with_index(ins, attrs, ctx):
    out, mask = _max_pool_with_index(ins["X"][0], attrs, 2)
    return {"Out": out, "Mask": mask}


@register_op("max_pool3d_with_index")
def max_pool3d_with_index(ins, attrs, ctx):
    out, mask = _max_pool_with_index(ins["X"][0], attrs, 3)
    return {"Out": out, "Mask": mask}


@register_op("unpool", nondiff_inputs=("Indices",))
def unpool(ins, attrs, ctx):
    """reference: unpool_op.cc ('max' unpooling) — scatter X into a zero
    output at the row-major positions recorded by max_pool2d_with_index;
    out_size = (in-1)*stride - 2*pad + ksize."""
    x, idx = ins["X"][0], ins["Indices"][0]
    n, c, h, w = x.shape
    ksize = [int(k) for k in attrs.get("ksize", [2, 2])]
    strides = [int(s) for s in attrs.get("strides", ksize)]
    pads = [int(p) for p in attrs.get("paddings", [0, 0])]
    oh = (h - 1) * strides[0] - 2 * pads[0] + ksize[0]
    ow = (w - 1) * strides[1] - 2 * pads[1] + ksize[1]
    flat = x.reshape(n * c, h * w)
    idxf = idx.reshape(n * c, h * w).astype(jnp.int32)
    rows = jnp.arange(n * c)[:, None]
    out = jnp.zeros((n * c, oh * ow), x.dtype).at[rows, idxf].set(flat)
    return {"Out": out.reshape(n, c, oh, ow)}


@register_op("spp")
def spp(ins, attrs, ctx):
    """reference: spp_op.h — spatial pyramid pooling: levels p=0..H-1 pool
    into 2^p x 2^p bins (kernel=ceil(dim/bins), pad=(k*bins-dim+1)/2),
    flattened and concatenated along channels."""
    x = ins["X"][0]
    n, c, h, w = x.shape
    height = int(attrs.get("pyramid_height", 1))
    ptype = attrs.get("pooling_type", "max")
    outs = []
    for p in range(height):
        bins = 2 ** p
        kh = -(-h // bins)
        kw = -(-w // bins)
        ph_ = (kh * bins - h + 1) // 2
        pw_ = (kw * bins - w + 1) // 2
        lvl = _pool2d(x, {"pooling_type": ptype, "ksize": [kh, kw],
                          "strides": [kh, kw], "paddings": [ph_, pw_],
                          "exclusive": True})
        outs.append(lvl.reshape(n, c * bins * bins))
    return {"Out": jnp.concatenate(outs, axis=1)}


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------


@register_op("batch_norm", nondiff_inputs=("Mean", "Variance"),
             intermediate_outputs=("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"))
def batch_norm(ins, attrs, ctx):
    """reference: operators/batch_norm_op.cc.

    NOTE (TPU semantics): under data-parallel GSPMD sharding the batch
    reductions below become *global* (cross-replica) reductions — i.e. this
    is automatically sync-BN (reference needs BuildStrategy.sync_batch_norm +
    sync_batch_norm_op.cu).
    """
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = bool(attrs.get("is_test", False)) or ctx.is_test
    use_global = bool(attrs.get("use_global_stats", False)) or is_test
    layout = attrs.get("data_layout", "NCHW")
    axes = tuple(i for i in range(x.ndim) if i != (1 if layout == "NCHW" else x.ndim - 1))
    ch_shape = [1] * x.ndim
    ch_shape[1 if layout == "NCHW" else -1] = x.shape[1 if layout == "NCHW" else -1]

    if use_global:
        m, v = mean, var
        y = (x - m.reshape(ch_shape)) * (scale.reshape(ch_shape) *
             jax.lax.rsqrt(v.reshape(ch_shape) + eps)) + bias.reshape(ch_shape)
        return {"Y": y, "MeanOut": mean, "VarianceOut": var,
                "SavedMean": mean, "SavedVariance": var}

    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=axes)
    v = jnp.mean(jnp.square(xf), axis=axes) - jnp.square(m)
    y = (xf - m.reshape(ch_shape)) * jax.lax.rsqrt(v.reshape(ch_shape) + eps)
    y = y.astype(x.dtype) * scale.reshape(ch_shape) + bias.reshape(ch_shape)
    new_mean = mean * momentum + m * (1.0 - momentum)
    new_var = var * momentum + v * (1.0 - momentum)
    return {"Y": y, "MeanOut": new_mean, "VarianceOut": new_var,
            "SavedMean": m, "SavedVariance": jax.lax.rsqrt(v + eps)}


@register_op("sync_batch_norm", nondiff_inputs=("Mean", "Variance"),
             intermediate_outputs=("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"))
def sync_batch_norm(ins, attrs, ctx):
    # identical to batch_norm: GSPMD makes batch reductions global
    return batch_norm(ins, attrs, ctx)


@register_op("layer_norm", intermediate_outputs=("Mean", "Variance"))
def layer_norm(ins, attrs, ctx):
    """reference: operators/layer_norm_op.cc (begin_norm_axis flattening)."""
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    bna = int(attrs.get("begin_norm_axis", 1))
    axes = tuple(range(bna, x.ndim))
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=axes, keepdims=True)
    v = jnp.mean(jnp.square(xf - m), axis=axes, keepdims=True)
    y = (xf - m) * jax.lax.rsqrt(v + eps)
    y = y.astype(x.dtype)
    norm_shape = x.shape[bna:]
    if ins.get("Scale") and ins["Scale"][0] is not None:
        y = y * ins["Scale"][0].reshape(norm_shape)
    if ins.get("Bias") and ins["Bias"][0] is not None:
        y = y + ins["Bias"][0].reshape(norm_shape)
    return {"Y": y, "Mean": m.reshape(x.shape[:bna]), "Variance": v.reshape(x.shape[:bna])}


@register_op("group_norm", intermediate_outputs=("Mean", "Variance"))
def group_norm(ins, attrs, ctx):
    x = ins["X"][0]  # NCHW
    groups = int(attrs["groups"])
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, groups, c // groups) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    m = jnp.mean(xg, axis=axes, keepdims=True)
    v = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - m) * jax.lax.rsqrt(v + eps)).reshape(x.shape)
    ch = [1, c] + [1] * (x.ndim - 2)
    if ins.get("Scale") and ins["Scale"][0] is not None:
        y = y * ins["Scale"][0].reshape(ch)
    if ins.get("Bias") and ins["Bias"][0] is not None:
        y = y + ins["Bias"][0].reshape(ch)
    return {"Y": y, "Mean": m.reshape(n, groups), "Variance": v.reshape(n, groups)}


@register_op("instance_norm", intermediate_outputs=("SavedMean", "SavedVariance"))
def instance_norm(ins, attrs, ctx):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    m = jnp.mean(x, axis=axes, keepdims=True)
    v = jnp.var(x, axis=axes, keepdims=True)
    y = (x - m) * jax.lax.rsqrt(v + eps)
    ch = [1, x.shape[1]] + [1] * (x.ndim - 2)
    if ins.get("Scale") and ins["Scale"][0] is not None:
        y = y * ins["Scale"][0].reshape(ch)
    if ins.get("Bias") and ins["Bias"][0] is not None:
        y = y + ins["Bias"][0].reshape(ch)
    return {"Y": y, "SavedMean": jnp.squeeze(m), "SavedVariance": jnp.squeeze(v)}


@register_op("l2_normalize")
def l2_normalize(ins, attrs, ctx):
    x = ins["X"][0]
    axis = int(attrs.get("axis", -1))
    eps = attrs.get("epsilon", 1e-10)
    return {"Out": x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)}


# ---------------------------------------------------------------------------
# Dropout / softmax
# ---------------------------------------------------------------------------


@register_op("dropout", is_random=True, intermediate_outputs=("Mask",))
def dropout(ins, attrs, ctx):
    """reference: operators/dropout_op.cc (upscale_in_train vs
    downgrade_in_infer implementations)."""
    x = ins["X"][0]
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    is_test = bool(attrs.get("is_test", False)) or ctx.is_test
    if is_test or p == 0.0:
        out = x if impl == "upscale_in_train" else x * (1.0 - p)
        return {"Out": out, "Mask": jnp.ones_like(x, dtype=jnp.uint8)}
    keep = jax.random.bernoulli(ctx.rng(), 1.0 - p, x.shape)
    if impl == "upscale_in_train":
        out = jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    else:
        out = jnp.where(keep, x, 0.0).astype(x.dtype)
    return {"Out": out, "Mask": keep.astype(jnp.uint8)}


@register_op("softmax")
def softmax(ins, attrs, ctx):
    x = ins["X"][0]
    axis = int(attrs.get("axis", -1))
    return {"Out": jax.nn.softmax(x, axis=axis)}


@register_op("log_softmax")
def log_softmax(ins, attrs, ctx):
    x = ins["X"][0]
    return {"Out": jax.nn.log_softmax(x, axis=int(attrs.get("axis", -1)))}


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------


@register_op("cross_entropy", nondiff_inputs=("Label",))
def cross_entropy(ins, attrs, ctx):
    """reference: operators/cross_entropy_op.cc — X is a probability
    distribution; hard or soft labels."""
    x, label = ins["X"][0], ins["Label"][0]
    ignore_index = int(attrs.get("ignore_index", -100))
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, 1e-20)), axis=-1, keepdims=True)
        return {"Y": loss}
    idx = label.astype(jnp.int32)
    if idx.ndim == x.ndim and idx.shape[-1] == 1:
        idx = idx[..., 0]
    picked = jnp.take_along_axis(x, idx[..., None], axis=-1)
    loss = -jnp.log(jnp.maximum(picked, 1e-20))
    if ignore_index != -100:
        loss = jnp.where(idx[..., None] == ignore_index, 0.0, loss)
    return {"Y": loss}


@register_op("softmax_with_cross_entropy", nondiff_inputs=("Label",),
             intermediate_outputs=("Softmax",))
def softmax_with_cross_entropy(ins, attrs, ctx):
    """reference: operators/softmax_with_cross_entropy_op.cc — numerically
    stable fused version (the BERT/Transformer loss)."""
    logits, label = ins["Logits"][0], ins["Label"][0]
    axis = int(attrs.get("axis", -1))
    lse = jax.scipy.special.logsumexp(logits, axis=axis, keepdims=True)
    log_probs = logits - lse
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * log_probs, axis=axis, keepdims=True)
    else:
        idx = label.astype(jnp.int32)
        if idx.ndim == logits.ndim and idx.shape[axis] == 1:
            idx = jnp.squeeze(idx, axis)
        picked = jnp.take_along_axis(log_probs, jnp.expand_dims(idx, axis), axis=axis)
        loss = -picked
        ignore_index = int(attrs.get("ignore_index", -100))
        if ignore_index >= 0:
            loss = jnp.where(jnp.expand_dims(idx, axis) == ignore_index, 0.0, loss)
    return {"Loss": loss, "Softmax": jnp.exp(log_probs)}


@register_op("sigmoid_cross_entropy_with_logits", nondiff_inputs=("Label",))
def sigmoid_cross_entropy_with_logits(ins, attrs, ctx):
    x, label = ins["X"][0], ins["Label"][0]
    loss = jnp.maximum(x, 0.0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore_index = int(attrs.get("ignore_index", -100))
    if ignore_index != -100:
        loss = jnp.where(label == ignore_index, 0.0, loss)
    if attrs.get("normalize", False):
        n = jnp.maximum(jnp.sum(label != ignore_index), 1.0)
        loss = loss / n
    return {"Out": loss}


@register_op("square_error_cost", nondiff_inputs=())
def square_error_cost(ins, attrs, ctx):
    x, y = ins["X"][0], ins["Y"][0]
    return {"Out": jnp.square(x - y)}


@register_op("smooth_l1_loss", nondiff_inputs=("InsideWeight", "OutsideWeight"),
             intermediate_outputs=("Diff",))
def smooth_l1_loss(ins, attrs, ctx):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    sigma2 = sigma * sigma
    diff = x - y
    if ins.get("InsideWeight") and ins["InsideWeight"][0] is not None:
        diff = diff * ins["InsideWeight"][0]
    abs_diff = jnp.abs(diff)
    loss = jnp.where(abs_diff < 1.0 / sigma2,
                     0.5 * sigma2 * jnp.square(diff),
                     abs_diff - 0.5 / sigma2)
    if ins.get("OutsideWeight") and ins["OutsideWeight"][0] is not None:
        loss = loss * ins["OutsideWeight"][0]
    return {"Out": jnp.sum(loss, axis=tuple(range(1, loss.ndim)), keepdims=False)[..., None],
            "Diff": diff}


@register_op("huber_loss", intermediate_outputs=("Residual",))
def huber_loss(ins, attrs, ctx):
    x, y = ins["X"][0], ins["Y"][0]
    delta = attrs.get("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= delta, 0.5 * jnp.square(r), delta * (ar - 0.5 * delta))
    return {"Out": loss, "Residual": r}


@register_op("kldiv_loss", nondiff_inputs=("Target",))
def kldiv_loss(ins, attrs, ctx):
    x, t = ins["X"][0], ins["Target"][0]
    loss = t * (jnp.log(jnp.maximum(t, 1e-20)) - x)
    red = attrs.get("reduction", "mean")
    if red == "mean":
        return {"Loss": jnp.mean(loss)}
    if red == "sum":
        return {"Loss": jnp.sum(loss)}
    if red == "batchmean":
        return {"Loss": jnp.sum(loss) / x.shape[0]}
    return {"Loss": loss}


@register_op("bce_loss", nondiff_inputs=("Label",))
def bce_loss(ins, attrs, ctx):
    x, label = ins["X"][0], ins["Label"][0]
    return {"Out": -(label * jnp.log(jnp.maximum(x, 1e-12))
                     + (1 - label) * jnp.log(jnp.maximum(1 - x, 1e-12)))}


@register_op("margin_rank_loss", nondiff_inputs=("Label",),
             intermediate_outputs=("Activated",))
def margin_rank_loss(ins, attrs, ctx):
    x1, x2, label = ins["X1"][0], ins["X2"][0], ins["Label"][0]
    margin = attrs.get("margin", 0.0)
    out = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": out, "Activated": (out > 0).astype(x1.dtype)}


@register_op("hinge_loss", nondiff_inputs=("Labels",))
def hinge_loss(ins, attrs, ctx):
    logits, labels = ins["Logits"][0], ins["Labels"][0]
    return {"Loss": jnp.maximum(0.0, 1.0 - (2.0 * labels - 1.0) * logits)}


# ---------------------------------------------------------------------------
# Interpolation / resampling
# ---------------------------------------------------------------------------


def _linear_resize_weights(s, o, align_corners, align_mode, dtype):
    """[o, s] interpolation-weight matrix for one axis (two taps per row).
    Source positions follow interpolate_op.h: align_corners →
    i*(s-1)/(o-1); align_mode 0 → (i+0.5)*s/o - 0.5; align_mode 1 →
    i*s/o."""
    if o == 1 or s == 1:
        pos = jnp.zeros((o,), dtype)
    elif align_corners:
        pos = jnp.arange(o, dtype=dtype) * (s - 1) / (o - 1)
    elif int(align_mode) == 0:
        pos = (jnp.arange(o, dtype=dtype) + 0.5) * s / o - 0.5
    else:
        pos = jnp.arange(o, dtype=dtype) * s / o
    pos = jnp.clip(pos, 0.0, s - 1)
    lo = jnp.floor(pos).astype(jnp.int32)
    hi = jnp.minimum(lo + 1, s - 1)
    frac = (pos - lo).astype(dtype)
    rows = jnp.arange(o)
    return jnp.zeros((o, s), dtype).at[rows, lo].add(1.0 - frac) \
        .at[rows, hi].add(frac)


def _interp(ins, attrs, method):
    """reference: interpolate_op.h — separable linear resize honoring
    align_corners/align_mode (each axis is one [O,S] weight matmul; XLA
    fuses the chain onto the MXU). nearest keeps jax.image.resize."""
    x = ins["X"][0]  # NC + spatial
    n, c = x.shape[:2]
    spatial = x.shape[2:]
    nd = len(spatial)
    keys = ("out_d", "out_h", "out_w")[-nd:]
    given = [k for k in keys if attrs.get(k, -1) > 0]
    if given:
        assert len(given) == nd, (
            f"interp on {nd}-D spatial input needs all of {keys}, "
            f"got only {given}")
        out_sp = tuple(int(attrs[k]) for k in keys)
    else:
        scale = attrs.get("scale", 1.0)
        out_sp = tuple(int(s * scale) for s in spatial)
    if method == "nearest":
        out = jax.image.resize(x, (n, c) + out_sp, method="nearest")
        return {"Out": out.astype(x.dtype)}
    ac = bool(attrs.get("align_corners", True))
    am = int(attrs.get("align_mode", 1))
    wdt = jnp.float32 if x.dtype == jnp.bfloat16 else x.dtype
    out = x.astype(wdt)
    for d in range(nd):
        wm = _linear_resize_weights(spatial[d], out_sp[d], ac, am, wdt)
        out = jnp.moveaxis(
            jnp.tensordot(wm, jnp.moveaxis(out, 2 + d, 0), axes=([1], [0])),
            0, 2 + d)
    return {"Out": out.astype(x.dtype)}


@register_op("bilinear_interp")
def bilinear_interp(ins, attrs, ctx):
    return _interp(ins, attrs, "bilinear")


@register_op("nearest_interp")
def nearest_interp(ins, attrs, ctx):
    return _interp(ins, attrs, "nearest")


@register_op("trilinear_interp")
def trilinear_interp(ins, attrs, ctx):
    """reference: interpolate_op.cc trilinear branch — 5-D NCDHW linear
    resize (resize_trilinear layer, nn.py:9716)."""
    return _interp(ins, attrs, "trilinear")


@register_op("grid_sampler")
def grid_sampler(ins, attrs, ctx):
    """reference: operators/grid_sampler_op.cc (cudnn spatial sampler) —
    bilinear sampling from normalized [-1,1] grid coords."""
    x, grid = ins["X"][0], ins["Grid"][0]  # x: NCHW, grid: NHW2
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1.0) * (w - 1) / 2.0
    gy = (grid[..., 1] + 1.0) * (h - 1) / 2.0
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1
    wx1, wy1 = gx - x0, gy - y0
    wx0, wy0 = 1.0 - wx1, 1.0 - wy1

    def sample(yy, xx):
        yy = jnp.clip(yy, 0, h - 1)
        xx = jnp.clip(xx, 0, w - 1)
        batch_idx = jnp.arange(n)[:, None, None]
        return x[batch_idx, :, yy, xx]  # N,H',W',C

    v00 = sample(y0, x0) * (wy0 * wx0)[..., None]
    v01 = sample(y0, x1) * (wy0 * wx1)[..., None]
    v10 = sample(y1, x0) * (wy1 * wx0)[..., None]
    v11 = sample(y1, x1) * (wy1 * wx1)[..., None]
    out = (v00 + v01 + v10 + v11).transpose(0, 3, 1, 2)
    return {"Output": out.astype(x.dtype)}


# ---------------------------------------------------------------------------
# Misc NN
# ---------------------------------------------------------------------------


@register_op("pixel_shuffle")
def pixel_shuffle(ins, attrs, ctx):
    x = ins["X"][0]
    r = int(attrs.get("upscale_factor", 1))
    n, c, h, w = x.shape
    out = x.reshape(n, c // (r * r), r, r, h, w)
    out = out.transpose(0, 1, 4, 2, 5, 3).reshape(n, c // (r * r), h * r, w * r)
    return {"Out": out}


@register_op("temporal_shift")
def temporal_shift(ins, attrs, ctx):
    x = ins["X"][0]
    seg = int(attrs["seg_num"])
    ratio = attrs.get("shift_ratio", 0.25)
    nt, c, h, w = x.shape
    n = nt // seg
    xr = x.reshape(n, seg, c, h, w)
    c1 = int(c * ratio)
    c2 = int(c * 2 * ratio)
    fwd = jnp.concatenate([xr[:, 1:, :c1], jnp.zeros_like(xr[:, :1, :c1])], axis=1)
    back = jnp.concatenate([jnp.zeros_like(xr[:, :1, c1:c2]), xr[:, :-1, c1:c2]], axis=1)
    rest = xr[:, :, c2:]
    return {"Out": jnp.concatenate([fwd, back, rest], axis=2).reshape(nt, c, h, w)}


@register_op("label_smooth", nondiff_inputs=("PriorDist",))
def label_smooth(ins, attrs, ctx):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 0.0)
    k = x.shape[-1]
    if ins.get("PriorDist") and ins["PriorDist"][0] is not None:
        return {"Out": (1 - eps) * x + eps * ins["PriorDist"][0]}
    return {"Out": (1 - eps) * x + eps / k}


@register_op("embedding_with_scaled_gradient", nondiff_inputs=("Ids",))
def embedding_with_scaled_gradient(ins, attrs, ctx):
    from .tensor import lookup_table_v2

    return lookup_table_v2(ins, attrs, ctx)


@register_op("fc")
def fc_op(ins, attrs, ctx):
    """reference: fc_op.cc (the fused inference fc): Out =
    act(flatten(X) @ W + b) with in_num_col_dims."""
    x = ins["Input"][0]
    w = ins["W"][0]
    b = (ins.get("Bias") or [None])[0]
    ncol = int(attrs.get("in_num_col_dims", 1))
    lead = x.shape[:ncol]
    x2 = x.reshape((int(np.prod(lead)), -1))
    out = x2 @ w.astype(x2.dtype)
    if b is not None:
        out = out + b.reshape(1, -1).astype(out.dtype)
    act = attrs.get("activation_type", "")
    if act == "relu":
        out = jax.nn.relu(out)
    elif act:
        raise ValueError(f"fc: unsupported activation {act}")
    return {"Out": out.reshape(tuple(lead) + (w.shape[1],))}


@register_op("depthwise_conv2d_transpose")
def depthwise_conv2d_transpose(ins, attrs, ctx):
    """reference: conv_transpose_op.cc depthwise registration — grouped
    transpose conv with groups == channels. ONE batched HLO: vmap over
    the channel axis (a Python per-channel loop would emit C separate
    convs)."""
    x, w = ins["Input"][0], ins["Filter"][0]   # w: [C, 1, kh, kw]
    strides = tuple(int(s) for s in attrs.get("strides", [1, 1]))
    dils = tuple(int(d) for d in attrs.get("dilations", [1, 1]))
    pads = [int(p) for p in attrs.get("paddings", [0, 0])]
    if len(pads) == 2:
        pad_pairs = [(pads[0], pads[0]), (pads[1], pads[1])]
    else:  # [top, bottom, left, right]
        pad_pairs = [(pads[0], pads[1]), (pads[2], pads[3])]
    padding = [((w.shape[2 + i] - 1) * dils[i] - lo,
                (w.shape[2 + i] - 1) * dils[i] - hi)
               for i, (lo, hi) in enumerate(pad_pairs)]

    def one_channel(xc, wc):
        # xc [N,1,H,W], wc [1,1,kh,kw]
        dn = jax.lax.conv_dimension_numbers(xc.shape, wc.shape,
                                            ("NCHW", "OIHW", "NCHW"))
        return jax.lax.conv_transpose(
            xc, wc, strides=strides, padding=padding, rhs_dilation=dils,
            dimension_numbers=dn, transpose_kernel=True)[:, 0]

    # [C, N, 1, H, W] per-channel inputs; vmap emits one batched conv
    xc = jnp.moveaxis(x, 1, 0)[:, :, None]
    out = jax.vmap(one_channel)(xc, w[:, None])   # [C, N, H', W']
    return {"Output": jnp.moveaxis(out, 0, 1)}
