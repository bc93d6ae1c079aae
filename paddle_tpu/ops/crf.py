"""Linear-chain CRF ops — sequence labeling (SRL, NER).

Reference behavior: operators/linear_chain_crf_op.{cc,h} (forward algorithm,
Transition layout [D+2, D]: row 0 = start weights, row 1 = end weights, rows
2.. = tag->tag transitions; output LogLikelihood is the *negative*
log-likelihood per sequence), operators/crf_decoding_op.h (Viterbi; with a
Label input the output becomes a 0/1 per-position correctness mask), and
operators/chunk_eval_op.h (IOB/IOE/IOBES/plain chunk precision/recall/F1).

TPU-native design: the reference iterates per-sequence over LoD slices with
normalized probabilities; here sequences are a padded [N, T, D] batch with a
[N] Length vector, the forward/Viterbi recursions are `lax.scan` over time in
log space (no L1 renormalisation needed), and the whole batch runs as one
XLA computation. Gradients come from jax.vjp of the scan (the reference
hand-writes the backward recursion). chunk_eval vectorizes the reference's
per-position chunk state machine so the metric runs in-graph on TPU (no
host callback).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..core.registry import register_op


def _crf_batch(emission, transition, lengths):
    """Log-partition and log-alpha for a padded batch. emission [N,T,D],
    transition [D+2,D], lengths [N] -> (logZ [N], alpha [N,T,D])."""
    n, t, d = emission.shape
    w_start, w_end, w_trans = transition[0], transition[1], transition[2:]
    lengths = lengths.astype(jnp.int32)

    alpha0 = w_start[None, :] + emission[:, 0, :]  # [N, D]

    def step(carry, xs):
        alpha_prev = carry
        x_k, k = xs
        # logsumexp_j(alpha[j] + trans[j, i]) + x[i]
        scores = alpha_prev[:, :, None] + w_trans[None, :, :]
        alpha_new = jax.nn.logsumexp(scores, axis=1) + x_k
        keep = (k < lengths)[:, None]
        alpha = jnp.where(keep, alpha_new, alpha_prev)
        return alpha, alpha

    xs = (jnp.moveaxis(emission[:, 1:, :], 1, 0), jnp.arange(1, t))
    alpha_last, alpha_rest = jax.lax.scan(step, alpha0, xs)
    alpha = jnp.concatenate([alpha0[:, None, :],
                             jnp.moveaxis(alpha_rest, 0, 1)], axis=1)
    logz = jax.nn.logsumexp(alpha_last + w_end[None, :], axis=-1)
    return logz, alpha


def _crf_score(emission, transition, label, lengths):
    """Score of the gold path, masked past each length. -> [N]."""
    n, t, d = emission.shape
    w_start, w_end, w_trans = transition[0], transition[1], transition[2:]
    lengths = lengths.astype(jnp.int32)
    lbl = label.astype(jnp.int32)
    pos = jnp.arange(t)[None, :]
    valid = pos < lengths[:, None]  # [N, T]

    emit = jnp.take_along_axis(emission, lbl[:, :, None], axis=2)[:, :, 0]
    emit_score = jnp.sum(jnp.where(valid, emit, 0.0), axis=1)

    trans = w_trans[lbl[:, :-1], lbl[:, 1:]]  # [N, T-1]
    trans_score = jnp.sum(jnp.where(valid[:, 1:], trans, 0.0), axis=1)

    last = jnp.maximum(lengths - 1, 0)
    last_lbl = jnp.take_along_axis(lbl, last[:, None], axis=1)[:, 0]
    return w_start[lbl[:, 0]] + emit_score + trans_score + w_end[last_lbl]


@register_op("linear_chain_crf", nondiff_inputs=("Label", "Length"),
             intermediate_outputs=("Alpha", "EmissionExps", "TransitionExps"))
def linear_chain_crf(ins, attrs, ctx):
    """NLL of gold tag paths under a linear-chain CRF.

    Inputs: Emission [N,T,D] (or [T,D] for one sequence), Transition [D+2,D],
    Label [N,T] int, Length [N] (optional; defaults to full T).
    Output LogLikelihood [N,1] = logZ - score (a cost, as in the reference).
    """
    emission = ins["Emission"][0]
    transition = ins["Transition"][0]
    label = ins["Label"][0]
    squeeze = emission.ndim == 2
    if squeeze:
        emission, label = emission[None], jnp.asarray(label).reshape(1, -1)
    if label.ndim == 3:  # [N,T,1] feed convention
        label = label[:, :, 0]
    n, t, d = emission.shape
    if ins.get("Length") and ins["Length"][0] is not None:
        lengths = ins["Length"][0].reshape(-1)
    else:
        lengths = jnp.full((n,), t, jnp.int32)
    logz, alpha = _crf_batch(emission, transition, lengths)
    score = _crf_score(emission, transition, label, lengths)
    nll = (logz - score)[:, None]
    return {"LogLikelihood": nll[0] if squeeze else nll,
            "Alpha": alpha,
            "EmissionExps": jnp.exp(emission),
            "TransitionExps": jnp.exp(transition)}


@register_op("crf_decoding", grad=None,
             nondiff_inputs=("Emission", "Transition", "Label", "Length"))
def crf_decoding(ins, attrs, ctx):
    """Viterbi decode. Output ViterbiPath [N,T] int64 (0 past length). When
    Label is given the output is 1 where decoded==label else 0, matching
    crf_decoding_op.h:69."""
    emission = ins["Emission"][0]
    transition = ins["Transition"][0]
    squeeze = emission.ndim == 2
    if squeeze:
        emission = emission[None]
    n, t, d = emission.shape
    if ins.get("Length") and ins["Length"][0] is not None:
        lengths = ins["Length"][0].reshape(-1).astype(jnp.int32)
    else:
        lengths = jnp.full((n,), t, jnp.int32)
    w_start, w_end, w_trans = transition[0], transition[1], transition[2:]

    alpha0 = w_start[None, :] + emission[:, 0, :]

    def fwd(carry, xs):
        alpha_prev = carry
        x_k, k = xs
        scores = alpha_prev[:, :, None] + w_trans[None, :, :]  # [N, D, D]
        best_prev = jnp.argmax(scores, axis=1)                 # [N, D]
        alpha_new = jnp.max(scores, axis=1) + x_k
        keep = (k < lengths)[:, None]
        alpha = jnp.where(keep, alpha_new, alpha_prev)
        return alpha, (best_prev, keep)

    xs = (jnp.moveaxis(emission[:, 1:, :], 1, 0), jnp.arange(1, t))
    alpha_last, (back, keeps) = jax.lax.scan(fwd, alpha0, xs)
    last_tag = jnp.argmax(alpha_last + w_end[None, :], axis=-1)  # [N]

    def bwd(carry, xs):
        tag = carry
        bp, keep = xs  # bp [N, D], keep [N, 1]
        prev = jnp.take_along_axis(bp, tag[:, None], axis=1)[:, 0]
        new_tag = jnp.where(keep[:, 0], prev, tag)
        # emit the tag at position k; positions >= length emit last valid tag
        return new_tag, jnp.where(keep[:, 0], tag, -1)

    first_tag, rev_path = jax.lax.scan(bwd, last_tag, (back, keeps),
                                       reverse=True)
    path = jnp.concatenate([first_tag[:, None],
                            jnp.moveaxis(rev_path, 0, 1)], axis=1)  # [N, T]
    # the reverse scan emits -1 only at invalid (k >= length) positions,
    # which this mask zeroes anyway
    pos = jnp.arange(t)[None, :]
    valid = pos < lengths[:, None]
    path = jnp.where(valid, path, 0)
    if ins.get("Label") and ins["Label"][0] is not None:
        lbl = ins["Label"][0]
        if lbl.ndim == 3:
            lbl = lbl[:, :, 0]
        if squeeze:
            lbl = jnp.asarray(lbl).reshape(1, -1)
        hit = (path == lbl.astype(path.dtype)) & valid
        path = hit.astype(jnp.int64)
    else:
        path = path.astype(jnp.int64)
    return {"ViterbiPath": path[0] if squeeze else path}


_SCHEMES = {
    # scheme -> (num_tag_types, begin, inside, end, single)
    "IOB": (2, 0, 1, -1, -1),
    "IOE": (2, -1, 0, 1, -1),
    "IOBES": (4, 0, 1, 2, 3),
    "plain": (1, -1, -1, -1, -1),
}


def _chunk_flags(labels, lengths, num_chunk_types, scheme):
    """Vectorized chunk state machine: per-position (begin, end-position,
    type) flags equivalent to the reference's ChunkBegin/ChunkEnd scan.
    Key fact making this exact: whenever ChunkBegin fires mid-run,
    ChunkEnd fires for the previous chunk, and every non-Other run starts
    with a begin — so chunks are runs of non-Other positions split at
    begin flags. Returns (begin [N,T] bool, ends [N,T] int32 = index of the
    chunk end for the chunk starting here, typ [N,T] int32)."""
    ntag, t_beg, t_in, t_end, t_sng = _SCHEMES[scheme]
    other = num_chunk_types
    lab = labels.astype(jnp.int32)
    n, t = lab.shape
    tag = lab % ntag
    typ = lab // ntag
    pos = jnp.arange(t, dtype=jnp.int32)
    valid = pos[None, :] < lengths.astype(jnp.int32)[:, None]
    typ = jnp.where(valid, typ, other)

    ptag = jnp.concatenate([jnp.full((n, 1), -1, tag.dtype),
                            tag[:, :-1]], axis=1)
    ptyp = jnp.concatenate([jnp.full((n, 1), other, typ.dtype),
                            typ[:, :-1]], axis=1)
    is_other = typ == other
    p_other = ptyp == other
    same_type = typ == ptyp
    tag_cond = ((tag == t_beg) | (tag == t_sng) |
                (((tag == t_in) | (tag == t_end)) &
                 ((ptag == t_end) | (ptag == t_sng))))
    begin = jnp.where(p_other, ~is_other,
                      jnp.where(is_other, False,
                                jnp.where(~same_type, True, tag_cond)))
    next_begin = jnp.concatenate(
        [begin[:, 1:], jnp.zeros((n, 1), bool)], axis=1)
    next_other = jnp.concatenate(
        [is_other[:, 1:], jnp.ones((n, 1), bool)], axis=1)
    end = (~is_other) & (next_other | next_begin)
    # for each position, the index of the next end at-or-after it
    end_idx = jnp.where(end, pos[None, :], t + 1)
    ends = jax.lax.cummin(end_idx, axis=1, reverse=True)
    return begin, ends, typ


@register_op("chunk_eval", grad=None,
             nondiff_inputs=("Inference", "Label", "SeqLength"))
def chunk_eval(ins, attrs, ctx):
    """Chunk precision/recall/F1 (reference: chunk_eval_op.h). The
    reference walks each LoD sequence with a state machine on the host;
    here the state machine is vectorized over the padded batch (shifted
    compares + a reverse cummin for chunk extents) so the metric runs
    in-graph on TPU."""
    inference = ins["Inference"][0]
    label = ins["Label"][0]
    if inference.ndim == 1:
        inference, label = inference[None], label[None]
    if inference.ndim == 3:
        inference, label = inference[:, :, 0], label[:, :, 0]
    n, t = inference.shape
    if ins.get("SeqLength") and ins["SeqLength"][0] is not None:
        seqlen = ins["SeqLength"][0].reshape(-1)
    else:
        seqlen = jnp.full((n,), t, jnp.int32)
    num_chunk_types = int(attrs["num_chunk_types"])
    scheme = attrs.get("chunk_scheme", "IOB")
    excluded = tuple(attrs.get("excluded_chunk_types", []) or [])

    bi, ei, ti = _chunk_flags(inference, seqlen, num_chunk_types, scheme)
    bl, el, tl = _chunk_flags(label, seqlen, num_chunk_types, scheme)

    def keep(typ):
        m = jnp.ones(typ.shape, bool)
        for e in excluded:
            m &= typ != int(e)
        return m

    int_dt = jnp.asarray(0, jnp.int64).dtype  # canonical int
    ni = jnp.sum(bi & keep(ti)).astype(int_dt)
    nl = jnp.sum(bl & keep(tl)).astype(int_dt)
    correct = bi & bl & (ti == tl) & (ei == el) & keep(ti)
    nc = jnp.sum(correct).astype(int_dt)

    p = jnp.where(ni > 0, nc / jnp.maximum(ni, 1), 0.0).astype(jnp.float32)
    r = jnp.where(nl > 0, nc / jnp.maximum(nl, 1), 0.0).astype(jnp.float32)
    f1 = jnp.where(nc > 0, 2 * p * r / jnp.maximum(p + r, 1e-12),
                   0.0).astype(jnp.float32)
    return {"Precision": p.reshape(1), "Recall": r.reshape(1),
            "F1-Score": f1.reshape(1), "NumInferChunks": ni.reshape(1),
            "NumLabelChunks": nl.reshape(1),
            "NumCorrectChunks": nc.reshape(1)}
