"""Classification metrics (reference: ``dask_ml/metrics/classification.py``).

Each metric is a single masked reduction over the sharded sample axis; with
sharded inputs XLA inserts the cross-device psum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.sharded import ShardedRows, masked_unique


def _lengths(a):
    if isinstance(a, ShardedRows):
        return a.n_samples, a.padded
    n = len(a) if not hasattr(a, "shape") else a.shape[0]  # lists welcome
    return n, n


def _align(y_true, y_pred):
    """Return (true, pred, mask) as padded device arrays of equal length.

    Mixed sharded/plain inputs of the same logical length are aligned by
    zero-padding the plain side up to the sharded side's padded length (the
    padded tail is masked out anyway).
    """
    n_t, pad_t = _lengths(y_true)
    n_p, pad_p = _lengths(y_pred)
    if n_t != n_p:
        raise ValueError(
            f"y_true and y_pred have different lengths: {n_t} vs {n_p}"
        )
    padded = max(pad_t, pad_p)

    def to_padded(a):
        x = a.data if isinstance(a, ShardedRows) else jnp.asarray(a)
        if x.shape[0] < padded:
            x = jnp.pad(x, [(0, padded - x.shape[0])] + [(0, 0)] * (x.ndim - 1))
        return x

    if isinstance(y_true, ShardedRows) and pad_t == padded:
        mask = y_true.mask
    elif isinstance(y_pred, ShardedRows) and pad_p == padded:
        mask = y_pred.mask
    else:
        mask = jnp.ones(padded, dtype=jnp.float32)
    return to_padded(y_true), to_padded(y_pred), mask


def _apply_weight(mask, sample_weight):
    if sample_weight is None:
        return mask
    w = sample_weight.data if isinstance(sample_weight, ShardedRows) else jnp.asarray(sample_weight)
    if w.shape[0] < mask.shape[0]:
        # host-side weights for a padded device array: pad with zeros
        w = jnp.pad(w, (0, mask.shape[0] - w.shape[0]))
    elif w.shape[0] > mask.shape[0]:
        # sharded (padded) weights for plain arrays: padded tail is zeros
        w = w[: mask.shape[0]]
    return mask * w


def accuracy_score(y_true, y_pred, normalize: bool = True, sample_weight=None, compute=True):
    """Fraction (or count) of correct predictions."""
    t, p, mask = _align(y_true, y_pred)
    w = _apply_weight(mask, sample_weight)
    correct = (t == p).astype(jnp.float32)
    hits = jnp.sum(correct * w)
    result = hits / jnp.sum(w) if normalize else hits
    return float(result) if compute else result


def log_loss(y_true, y_pred, eps="auto", normalize: bool = True, sample_weight=None, labels=None):
    """Negative log-likelihood of a classifier's probabilistic predictions.

    ``y_pred`` may be (n, k) probabilities or (n,) positive-class probability.
    ``eps="auto"`` clips at the INPUT's machine epsilon (sklearn semantics:
    a float64 probability of 0 contributes log(2.2e-16), not log(1e-15) —
    the clip level, not the log arithmetic, is what parity depends on).
    """
    if eps == "auto":
        # read the dtype WITHOUT materializing device data on host
        # (np.asarray of a jax array transfers; of a ShardedRows it makes
        # an object scalar); f32 inputs need f32's eps or the upper clip
        # 1-eps rounds back to 1.0 and log(1-p) overflows to -inf
        in_dtype = getattr(y_pred, "dtype", None)
        if in_dtype is None:
            in_dtype = np.asarray(y_pred).dtype
        # jnp.finfo: recognizes ml_dtypes floats (bfloat16) that
        # np.issubdtype rejects — falling back to float64 eps for bf16
        # would clip above bf16 resolution and let p==1.0 reach log(0)
        if jnp.issubdtype(in_dtype, jnp.floating):
            eps = float(jnp.finfo(in_dtype).eps)
        else:
            eps = float(np.finfo(np.float64).eps)
    t, p, mask = _align(y_true, y_pred)
    w = _apply_weight(mask, sample_weight)
    p = jnp.clip(p, eps, 1.0 - eps)
    if p.ndim == 1:
        per = -(t * jnp.log(p) + (1.0 - t) * jnp.log(1.0 - p))
    else:
        n_classes = p.shape[1]
        if labels is not None:
            labels = np.sort(np.asarray(labels))
            t_host = np.asarray(t).astype(np.int64)
            unseen = np.setdiff1d(np.unique(t_host), labels)
            if unseen.size:
                raise ValueError(
                    f"y_true contains labels not in `labels`: {unseen.tolist()}"
                )
            t = jnp.asarray(np.searchsorted(labels, t_host))
        onehot = jax.nn.one_hot(t.astype(jnp.int32), n_classes, dtype=p.dtype)
        p = p / jnp.sum(p, axis=1, keepdims=True)
        per = -jnp.sum(onehot * jnp.log(p), axis=1)
    total = jnp.sum(per * w)
    return float(total / jnp.sum(w)) if normalize else float(total)


def _class_inventory(t, p, mask, labels):
    """Sorted class values for P/R/F: from ``labels`` if given, else the
    union of true+predicted REAL values discovered on device (only the
    unique values cross to host)."""
    if labels is not None:
        # CALLER's order is the output order for average=None (sklearn
        # contract) — do not sort
        return np.asarray(labels)
    found = masked_unique(t, mask)
    if p is t:  # one vector (roc_auc_score's y_true): one scan
        return found
    return np.union1d(found, masked_unique(p, mask))


def _indicator_matrices(y_true, y_pred, sample_weight, labels):
    """Shared preamble of the count-based metrics: class inventory and
    the per-class one-hot indicators, plus the per-row weights."""
    t, p, mask = _align(y_true, y_pred)
    w = _apply_weight(mask, sample_weight)
    classes = _class_inventory(t, p, mask, labels)
    cd = jnp.asarray(classes, t.dtype)
    t1 = (t[:, None] == cd[None, :]).astype(jnp.float32)
    p1 = (p[:, None] == cd[None, :]).astype(jnp.float32)
    return classes, t1, p1, w


_COUNT_CHUNK = 1 << 22  # rows per f32 device partial sum: keeps every
# per-chunk count below 2^24, where f32 accumulation saturates

_AUC_BLOCK = 1 << 20  # roc_auc two-level prefix sum: within-block f32
# cumsums stay far below the 2^24 saturation point; block bases
# accumulate in float64 on host (tests shrink this to hit multi-block)


def _prf_counts(y_true, y_pred, sample_weight, labels):
    """Per-class (tp, pred_pos, true_pos) via one-hot products — no
    confusion-matrix scatter (slow on XLA:TPU).  Chunked with host
    float64 accumulation so counts stay exact past f32's 2^24 (same
    discipline as confusion_matrix)."""
    classes, t1, p1, w = _indicator_matrices(
        y_true, y_pred, sample_weight, labels
    )
    k = len(classes)
    tp = np.zeros(k, np.float64)
    pred_pos = np.zeros(k, np.float64)
    true_pos = np.zeros(k, np.float64)
    n = t1.shape[0]
    for lo in range(0, n, _COUNT_CHUNK):
        hi = min(lo + _COUNT_CHUNK, n)
        # weight each ROW once (weighting both indicators would square w
        # in the tp term)
        wc = w[lo:hi, None]
        tb, pb = t1[lo:hi], p1[lo:hi]
        tp += np.asarray(jnp.sum(tb * pb * wc, axis=0), np.float64)
        pred_pos += np.asarray(jnp.sum(pb * wc, axis=0), np.float64)
        true_pos += np.asarray(jnp.sum(tb * wc, axis=0), np.float64)
    return classes, tp, pred_pos, true_pos


def _prf(y_true, y_pred, *, average, sample_weight, labels, pos_label, beta=1.0):
    classes, tp, pp, tpos = _prf_counts(y_true, y_pred, sample_weight, labels)

    def safe(num, den):
        return np.where(den > 0, num / np.maximum(den, 1e-30), 0.0)

    prec = safe(tp, pp)
    rec = safe(tp, tpos)
    b2 = beta * beta
    f = safe((1 + b2) * prec * rec, b2 * prec + rec)
    if average == "binary":
        if len(classes) > 2:
            raise ValueError(
                "Target is multiclass but average='binary'; choose "
                "average from {'micro', 'macro', 'weighted', None} "
                f"(observed labels: {classes.tolist()})"
            )
        where = np.flatnonzero(classes == pos_label)
        if where.size == 0:
            if labels is not None:
                # the caller spelled out the label set: a pos_label not
                # in it is a coding error, not a thin CV fold — raise
                # like sklearn instead of silently scoring 0
                raise ValueError(
                    f"pos_label={pos_label!r} is not a valid label: "
                    f"{classes.tolist()}"
                )
            # sklearn semantics: an absent pos_label scores 0 with an
            # UndefinedMetricWarning, it does not abort the CV loop
            import warnings

            from sklearn.exceptions import UndefinedMetricWarning

            warnings.warn(
                f"pos_label={pos_label!r} not in observed labels "
                f"{classes.tolist()}; scores are 0.0",
                UndefinedMetricWarning, stacklevel=3,
            )
            return 0.0, 0.0, 0.0
        i = int(where[0])
        return float(prec[i]), float(rec[i]), float(f[i])
    if average == "macro":
        return float(prec.mean()), float(rec.mean()), float(f.mean())
    if average == "micro":
        P = safe(tp.sum(), pp.sum())
        R = safe(tp.sum(), tpos.sum())
        F = safe((1 + b2) * P * R, b2 * P + R)
        return float(P), float(R), float(F)
    if average == "weighted":
        wts = tpos / max(tpos.sum(), 1e-30)
        return (
            float((prec * wts).sum()),
            float((rec * wts).sum()),
            float((f * wts).sum()),
        )
    if average is None:
        return prec, rec, f
    raise ValueError(f"Unsupported average: {average!r}")


def precision_score(y_true, y_pred, *, average="binary", pos_label=1,
                    sample_weight=None, labels=None):
    """tp / (tp + fp), per sklearn semantics (binary/micro/macro/weighted
    or per-class with average=None); counts reduce on device."""
    return _prf(y_true, y_pred, average=average, sample_weight=sample_weight,
                labels=labels, pos_label=pos_label)[0]


def recall_score(y_true, y_pred, *, average="binary", pos_label=1,
                 sample_weight=None, labels=None):
    """tp / (tp + fn), per sklearn semantics."""
    return _prf(y_true, y_pred, average=average, sample_weight=sample_weight,
                labels=labels, pos_label=pos_label)[1]


def f1_score(y_true, y_pred, *, average="binary", pos_label=1,
             sample_weight=None, labels=None):
    """Harmonic mean of precision and recall, per sklearn semantics."""
    return _prf(y_true, y_pred, average=average, sample_weight=sample_weight,
                labels=labels, pos_label=pos_label)[2]


def roc_auc_score(y_true, y_score, sample_weight=None):
    """Binary ROC AUC via the rank (Mann-Whitney U) formulation.

    One device sort + two vectorized binary searches — exact under score
    ties (tied positive/negative pairs count 0.5) and sample weights, and
    pad rows drop out through their zero weight:
    ``AUC = sum over positives of w * (W_neg_below + W_neg_tied / 2)
    / (W_pos * W_neg)``.
    """
    t, s, mask = _align(y_true, y_score)
    w = _apply_weight(mask, sample_weight)
    classes = _class_inventory(t, t, mask, None)
    if len(classes) != 2:
        raise ValueError(
            "roc_auc_score needs exactly 2 classes in y_true; got "
            f"{classes.tolist()}"
        )
    pos = (t == jnp.asarray(classes[1], t.dtype)).astype(jnp.float32)
    # keep the scores' own floating dtype: a cast would create spurious
    # ties between scores that differ below the narrower resolution
    # (under default JAX config device floats are at most f32; enable
    # x64 for float64-exact tie handling)
    if not jnp.issubdtype(s.dtype, jnp.floating):
        s = s.astype(jnp.float32)
    # pad rows: weight 0 — push them to the front so real ties are intact
    s = jnp.where(mask > 0, s, -jnp.inf)
    order = jnp.argsort(s)
    s_sorted = s[order]
    wneg_sorted = (w * (1.0 - pos))[order]
    lo = jnp.searchsorted(s_sorted, s, side="left")
    hi = jnp.searchsorted(s_sorted, s, side="right")
    wpos = w * pos
    # below + tied/2 at index j is 0.5*(cum(lo_j) + cum(hi_j)) where cum
    # is the exclusive prefix sum of negative weight.  A single f32
    # cumsum loses unit precision past 2^24 accumulated weight, so the
    # prefix sum is TWO-LEVEL: within-block cumsums stay on device in
    # f32 (exact at block scale), while the O(B) block bases accumulate
    # in float64 on host — fetches are B-sized, never O(n).
    n_tot = int(s.shape[0])
    L = _AUC_BLOCK
    while L >= 2 * max(n_tot, 1):
        L >>= 1
    B = -(-n_tot // L)
    n_pad = B * L
    wneg_p = jnp.zeros((n_pad,), jnp.float32).at[:n_tot].set(wneg_sorted)
    blocks = wneg_p.reshape(B, L)
    within_incl = jnp.cumsum(blocks, axis=1)
    block_sums = within_incl[:, -1]
    within_excl = (within_incl - blocks).reshape(-1)
    # index n_pad is reachable only when hi == n_tot == n_pad: zero
    # within-block prefix, block id B (whose base is the full W_neg)
    flat_within = jnp.concatenate(
        [within_excl, jnp.zeros((1,), jnp.float32)]
    )
    # EVERY n-length accumulation is chunked with float64 host combines —
    # a single f32 device sum saturates at 2^24 accumulated unit weight,
    # the exact regime this two-level path exists for
    ids = jnp.concatenate([lo // L, hi // L])
    wps = jnp.concatenate([wpos, wpos])
    seg64 = np.zeros(B + 1, np.float64)
    for c0 in range(0, 2 * n_tot, _COUNT_CHUNK):
        c1 = min(c0 + _COUNT_CHUNK, 2 * n_tot)
        seg64 += np.asarray(
            jax.ops.segment_sum(
                wps[c0:c1], ids[c0:c1], num_segments=B + 1
            ),
            np.float64,
        )
    num_within64 = 0.0
    W_pos = 0.0
    half_inner = wpos * 0.5 * (flat_within[lo] + flat_within[hi])
    for c0 in range(0, n_tot, _COUNT_CHUNK):
        c1 = min(c0 + _COUNT_CHUNK, n_tot)
        num_within64 += float(jnp.sum(half_inner[c0:c1]))
        W_pos += float(jnp.sum(wpos[c0:c1]))
    bases = np.concatenate(
        [[0.0], np.cumsum(np.asarray(block_sums, np.float64))]
    )
    num = num_within64 + 0.5 * float(seg64 @ bases)
    W_neg = float(bases[-1])
    denom = W_pos * W_neg
    if denom <= 0:
        raise ValueError("Only one class present after weighting")
    return num / denom


def confusion_matrix(y_true, y_pred, *, labels=None, sample_weight=None,
                     normalize=None):
    """Confusion matrix C with C[i, j] = weight of samples of true class i
    predicted as class j — ONE device gemm (true-one-hot^T @ weighted
    pred-one-hot), no scatter (slow on XLA:TPU).
    """
    classes, t1, p1, w = _indicator_matrices(
        y_true, y_pred, sample_weight, labels
    )
    # chunked accumulation: a single f32 gemm silently saturates counts
    # at 2^24; per-chunk partial matrices stay exact (chunk < 2^22 rows)
    # and are summed in float64 ON HOST — the k x k result never goes
    # back to device (jnp would downcast the f64 sums without x64)
    n_rows = t1.shape[0]
    chunk = _COUNT_CHUNK
    hi_prec = jax.lax.Precision.HIGHEST  # default MXU bf16 would
    # truncate weights to 8 mantissa bits
    cm = np.zeros((len(classes), len(classes)), np.float64)
    for lo in range(0, n_rows, chunk):
        hi = min(lo + chunk, n_rows)
        cm += np.asarray(
            jnp.dot(t1[lo:hi].T, p1[lo:hi] * w[lo:hi, None],
                    precision=hi_prec),
            dtype=np.float64,
        )
    if normalize == "true":
        denom = cm.sum(axis=1, keepdims=True)
    elif normalize == "pred":
        denom = cm.sum(axis=0, keepdims=True)
    elif normalize == "all":
        denom = np.asarray(cm.sum())
    elif normalize is None:
        denom = None
    else:
        raise ValueError(f"Unsupported normalize: {normalize!r}")
    if denom is not None:
        with np.errstate(divide="ignore", invalid="ignore"):
            cm = cm / denom
        # sklearn nan_to_nums the zero-support rows/cols (verified
        # empirically; its docs read as NaN but the code zero-fills)
        return np.nan_to_num(cm)
    if sample_weight is None:
        return cm.astype(np.int64)
    return cm


def balanced_accuracy_score(y_true, y_pred, *, sample_weight=None,
                            adjusted=False):
    """Mean per-class recall over classes PRESENT in ``y_true`` (sklearn
    drops classes with no true samples before averaging — a plain macro
    recall would count a predicted-only class as recall 0)."""
    _, tp, _, tpos = _prf_counts(y_true, y_pred, sample_weight, None)
    present = tpos > 0
    if not present.any():
        raise ValueError("y_true has no represented classes")
    rec = tp[present] / tpos[present]
    score = float(rec.mean())
    if adjusted:
        chance = 1.0 / int(present.sum())
        score = (score - chance) / (1.0 - chance)
    return score
