"""Metric ops (reference: /root/reference/paddle/fluid/operators/metrics/
accuracy_op.cc, auc_op.cc, precision_recall_op.cc)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import REQUIRED, register_op


@register_op("accuracy", inputs=("Out", "Indices", "Label"),
             outputs=("Accuracy", "Correct", "Total"),
             differentiable=False)
def accuracy(ins, attrs):
    """Indices: [N, k] top-k predictions; Label: [N, 1]."""
    idx, label = ins["Indices"], ins["Label"]
    lab = label.reshape(-1, 1)
    correct = jnp.any(idx == lab, axis=1)
    num_correct = jnp.sum(correct.astype(jnp.float32))
    int_t = jax.dtypes.canonicalize_dtype(jnp.int64)
    total = jnp.asarray(idx.shape[0], int_t)
    return {
        "Accuracy": num_correct / idx.shape[0],
        "Correct": num_correct.astype(int_t),
        "Total": total,
    }


@register_op("auc", inputs=("Predict", "Label", "StatPos", "StatNeg"),
             outputs=("AUC", "StatPosOut", "StatNegOut"),
             attrs={"num_thresholds": 4095, "curve": "ROC"},
             differentiable=False,
             in_place={"StatPosOut": "StatPos", "StatNegOut": "StatNeg"})
def auc(ins, attrs):
    """Streaming AUC via threshold buckets (reference auc_op.cc)."""
    pred, label = ins["Predict"], ins["Label"]
    pos_hist, neg_hist = ins["StatPos"], ins["StatNeg"]
    n = attrs["num_thresholds"]
    p1 = pred[:, -1] if pred.ndim == 2 else pred.reshape(-1)
    bucket = jnp.clip((p1 * n).astype(jnp.int32), 0, n)
    lab = label.reshape(-1).astype(jnp.bool_)
    pos_hist = pos_hist.at[bucket].add(lab.astype(pos_hist.dtype))
    neg_hist = neg_hist.at[bucket].add((~lab).astype(neg_hist.dtype))
    # integrate over descending threshold
    pos_cum = jnp.cumsum(pos_hist[::-1])
    neg_cum = jnp.cumsum(neg_hist[::-1])
    tot_pos = pos_cum[-1]
    tot_neg = neg_cum[-1]
    # trapezoid on (fpr, tpr)
    tpr = pos_cum / jnp.maximum(tot_pos, 1)
    fpr = neg_cum / jnp.maximum(tot_neg, 1)
    auc_val = jnp.sum(
        (fpr[1:] - fpr[:-1]) * (tpr[1:] + tpr[:-1]) / 2.0
    ) + fpr[0] * tpr[0] / 2.0
    return {"AUC": auc_val, "StatPosOut": pos_hist, "StatNegOut": neg_hist}


@register_op("step_stat", inputs=("X", "Ring", "Step"),
             outputs=("RingOut",),
             attrs={"name": REQUIRED, "columns": REQUIRED},
             differentiable=False, in_place={"RingOut": "Ring"})
def step_stat(ins, attrs):
    """One row a step of what the compiled step says of itself
    (layers.step_stat, observability/step_stats.py): X, a small vector,
    is written as float32 into row (Step - 1) mod K of Ring [K, width].
    Step [1] is the program's count of steps, incremented before any
    stat is written, so the step with index i (from 0) writes row
    i mod K.  `name` and `columns` are for the reader: the compute
    reads neither."""
    ring = ins["Ring"]
    row = (ins["Step"].reshape(()) - 1) % ring.shape[0]
    x = ins["X"].astype(ring.dtype).reshape(1, ring.shape[1])
    return {"RingOut": jax.lax.dynamic_update_slice(ring, x, (row, 0))}


@register_op("precision_recall",
             inputs=("MaxProbs", "Indices", "Labels", "StatesInfo"),
             outputs=("BatchMetrics", "AccumMetrics", "AccumStatesInfo"),
             optional=("StatesInfo",),
             attrs={"class_number": REQUIRED}, differentiable=False)
def precision_recall(ins, attrs):
    import jax

    c = attrs["class_number"]
    idx = ins["Indices"].reshape(-1).astype(jnp.int32)
    lab = ins["Labels"].reshape(-1).astype(jnp.int32)
    tp = jax.ops.segment_sum(
        (idx == lab).astype(jnp.float64), lab, num_segments=c
    )
    pred_cnt = jax.ops.segment_sum(
        jnp.ones_like(idx, jnp.float64), idx, num_segments=c
    )
    lab_cnt = jax.ops.segment_sum(
        jnp.ones_like(lab, jnp.float64), lab, num_segments=c
    )
    fp = pred_cnt - tp
    fn = lab_cnt - tp
    states = jnp.stack([tp, fp, fn, jnp.zeros_like(tp)], axis=1)
    if "StatesInfo" in ins:
        states = states + ins["StatesInfo"]
    def metrics(tp, fp, fn):
        precision = jnp.where(tp + fp > 0, tp / (tp + fp), 0.0)
        recall = jnp.where(tp + fn > 0, tp / (tp + fn), 0.0)
        f1 = jnp.where(precision + recall > 0,
                       2 * precision * recall / (precision + recall), 0.0)
        return jnp.asarray([jnp.mean(precision), jnp.mean(recall),
                            jnp.mean(f1),
                            jnp.sum(tp) / jnp.maximum(
                                jnp.sum(tp + fp), 1.0),
                            jnp.sum(tp) / jnp.maximum(
                                jnp.sum(tp + fn), 1.0),
                            0.0])
    batch = metrics(tp, fp, fn)
    acc = metrics(states[:, 0], states[:, 1], states[:, 2])
    return {"BatchMetrics": batch, "AccumMetrics": acc,
            "AccumStatesInfo": states}


# ---------------------------------------------------------------------------
# chunk_eval (reference operators/chunk_eval_op.h: GetSegments/ChunkBegin/
# ChunkEnd).  Chunk decoding is data-dependent sequential control flow, so it
# runs on host (host_only) like the reference's CPU-only kernel; padded
# [B, T](+SeqLength) replaces the LoD input.
# ---------------------------------------------------------------------------

_CHUNK_SCHEMES = {
    # scheme: (num_tag_types, tag_begin, tag_inside, tag_end, tag_single)
    "IOB": (2, 0, 1, -1, -1),
    "IOE": (2, -1, 0, 1, -1),
    "IOBES": (4, 0, 1, 2, 3),
    "plain": (1, -1, -1, -1, -1),
}


def _chunk_segments(seq, scheme, num_chunk_types):
    """Decode one tag sequence into [(begin, end, type)] chunks
    (reference chunk_eval_op.h:41 GetSegments)."""
    num_tag, t_begin, t_inside, t_end, t_single = _CHUNK_SCHEMES[scheme]
    other = num_chunk_types

    def chunk_end(ptag, ptype, tag, typ):
        # reference chunk_eval_op.h:83 ChunkEnd
        if ptype == other:
            return False
        if typ == other or typ != ptype:
            return True
        return ptag in (t_end, t_single) or (
            ptag in (t_begin, t_inside) and tag in (t_begin, t_single))

    def chunk_begin(ptag, ptype, tag, typ):
        # reference chunk_eval_op.h:96 ChunkBegin
        if ptype == other:
            return typ != other
        if typ == other:
            return False
        if typ != ptype:
            return True
        if tag == t_begin or tag == t_single:
            return True
        if tag in (t_inside, t_end):
            return ptag in (t_end, t_single)
        return False

    segments = []
    tag = typ = -1
    in_chunk = False
    start = 0
    for i, lab in enumerate(seq):
        ptag, ptype = tag, typ
        lab = int(lab)
        tag = lab % num_tag
        typ = lab // num_tag
        if in_chunk and chunk_end(ptag, ptype, tag, typ):
            segments.append((start, i - 1, ptype))
            in_chunk = False
        if chunk_begin(ptag, ptype, tag, typ):
            start = i
            in_chunk = True
    if in_chunk:
        segments.append((start, len(seq) - 1, typ))
    return segments


@register_op("chunk_eval",
             inputs=("Inference", "Label", "SeqLength"),
             outputs=("Precision", "Recall", "F1-Score", "NumInferChunks",
                      "NumLabelChunks", "NumCorrectChunks"),
             optional=("SeqLength",),
             attrs={"num_chunk_types": REQUIRED, "chunk_scheme": "IOB",
                    "excluded_chunk_types": []},
             differentiable=False, host_only=True)
def chunk_eval(ins, attrs):
    """Precision/recall/F1 of chunk detection over IOB/IOE/IOBES/plain
    tagging (reference chunk_eval_op.h:109 Compute)."""
    import numpy as np

    scheme = attrs["chunk_scheme"]
    if scheme not in _CHUNK_SCHEMES:
        raise ValueError(f"Unknown chunk scheme {scheme!r}")
    nct = int(attrs["num_chunk_types"])
    excluded = set(attrs.get("excluded_chunk_types") or [])
    inf = np.asarray(ins["Inference"]).reshape(
        np.asarray(ins["Inference"]).shape[0], -1)
    lab = np.asarray(ins["Label"]).reshape(inf.shape[0], -1)
    seq_len = ins.get("SeqLength")
    lens = (np.full((inf.shape[0],), inf.shape[1], np.int64)
            if seq_len is None else np.asarray(seq_len).reshape(-1))
    n_inf = n_lab = n_correct = 0
    for b in range(inf.shape[0]):
        L = int(lens[b])
        inf_seg = [s for s in _chunk_segments(inf[b, :L], scheme, nct)
                   if s[2] not in excluded]
        lab_seg = [s for s in _chunk_segments(lab[b, :L], scheme, nct)
                   if s[2] not in excluded]
        n_inf += len(inf_seg)
        n_lab += len(lab_seg)
        n_correct += len(set(inf_seg) & set(lab_seg))
    precision = n_correct / n_inf if n_inf else 0.0
    recall = n_correct / n_lab if n_lab else 0.0
    f1 = (2 * precision * recall / (precision + recall)
          if n_correct else 0.0)
    return {"Precision": np.asarray([precision], np.float32),
            "Recall": np.asarray([recall], np.float32),
            "F1-Score": np.asarray([f1], np.float32),
            "NumInferChunks": np.asarray([n_inf], np.int64),
            "NumLabelChunks": np.asarray([n_lab], np.int64),
            "NumCorrectChunks": np.asarray([n_correct], np.int64)}


@register_op("positive_negative_pair",
             inputs=("Score", "Label", "QueryID",
                     "AccumulatePositivePair", "AccumulateNegativePair",
                     "AccumulateNeutralPair", "Weight"),
             outputs=("PositivePair", "NegativePair", "NeutralPair"),
             optional=("AccumulatePositivePair",
                       "AccumulateNegativePair",
                       "AccumulateNeutralPair", "Weight"),
             attrs={"column": -1},
             differentiable=False, host_only=True)
def positive_negative_pair(ins, attrs):
    """positive_negative_pair_op.h: per-query ranking pair counts —
    for every doc pair with different labels, score order agreeing with
    label order counts positive, disagreeing negative, ties neutral;
    pair weight = mean of the two doc weights.  Host metric op (hash-map
    grouping) like the reference's CPU-only kernel."""
    import numpy as np

    score = np.asarray(ins["Score"])
    col = int(attrs.get("column", -1))
    if score.ndim > 1:
        width = score.shape[1]
        if col < 0:
            col += width
        score = score[:, col]
    score = score.reshape(-1)
    label = np.asarray(ins["Label"]).reshape(-1)
    query = np.asarray(ins["QueryID"]).reshape(-1)
    weight = ins.get("Weight")
    weight = (np.ones_like(score) if weight is None
              else np.asarray(weight).reshape(-1))
    pos = neg = neu = 0.0
    acc = ins.get("AccumulatePositivePair")
    if acc is not None:
        pos = float(np.asarray(acc).ravel()[0])
        neg = float(np.asarray(
            ins["AccumulateNegativePair"]).ravel()[0])
        neu = float(np.asarray(
            ins["AccumulateNeutralPair"]).ravel()[0])
    by_query = {}
    for i in range(score.shape[0]):
        by_query.setdefault(int(query[i]), []).append(
            (float(score[i]), float(label[i]), float(weight[i])))
    for docs in by_query.values():
        for a in range(len(docs)):
            for b in range(a + 1, len(docs)):
                s1, l1, w1 = docs[a]
                s2, l2, w2 = docs[b]
                if l1 == l2:
                    continue
                w = 0.5 * (w1 + w2)
                # reference parity (positive_negative_pair_op.h:94-99):
                # a tie adds to neutral AND falls through the ternary
                # into negative — deliberately no elif here
                if s1 == s2:
                    neu += w
                if (s1 - s2) * (l1 - l2) > 0.0:
                    pos += w
                else:
                    neg += w
    return {"PositivePair": np.asarray([pos], np.float32),
            "NegativePair": np.asarray([neg], np.float32),
            "NeutralPair": np.asarray([neu], np.float32)}
