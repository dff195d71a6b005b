"""The comparisons that decide ``correct``. Outside the timed window.

Every check returns ``{"name", "ok", ...numbers}``; a run is correct when
every check is ok. Tolerances come from the configuration file (``correct``)
with their reasons; they are read here, never defaulted.
"""
import math

import numpy as np


def check_losses(losses, first_k: int = 8) -> dict:
    """Every step's loss is finite, and the pool is being learnt: the mean
    of the last ``first_k`` steps lies below the mean of the first."""
    losses = [float(x) for x in losses]
    bad = [i for i, x in enumerate(losses) if not math.isfinite(x)]
    k = min(first_k, max(1, len(losses) // 2))
    head = float(np.mean(losses[:k])) if losses else float("nan")
    tail = float(np.mean(losses[-k:])) if losses else float("nan")
    return {"name": "loss_finite_and_falling", "non_finite_steps": len(bad),
            "first_mean": head, "last_mean": tail,
            "ok": bool(losses) and not bad and tail < head}


def check_loss_vs_reference(engine_loss: float, ref_loss: float,
                            rel_tol: float) -> dict:
    """The engine's loss on a seeded batch against the plain reference's on
    the same stored parameters."""
    rel = abs(engine_loss - ref_loss) / abs(ref_loss)
    return {"name": "loss_vs_reference", "engine": float(engine_loss),
            "reference": float(ref_loss), "rel_err": float(rel),
            "rel_tol": float(rel_tol),
            "ok": math.isfinite(rel) and rel <= rel_tol}


def check_tokens_vs_reference(samples, reference, margin: float,
                              min_checked_share: float,
                              min_agreement: float,
                              max_mismatch_share: float = 0.0) -> dict:
    """Greedy tokens against one teacher-forced reference forward.

    ``samples``: (prompt ids, generated ids) of finished requests. The
    reference runs ONE forward over prompt + generated; at every generated
    position where its top-1 logit leads its top-2 by more than ``margin``
    the engine's token must be the reference argmax. Positions inside the
    margin are near-ties that rounding may flip, and are not judged — but
    at least ``min_checked_share`` of all positions must be judged, so a
    margin cannot hide the comparison, and the plain agreement over ALL
    positions must reach ``min_agreement``, so arithmetic that is broadly
    noisier than the configuration states fails even inside the margin.
    ``max_mismatch_share`` (0 for a dense model) is the share of JUDGED
    positions that may still disagree: a sparse-expert model picks experts
    by a hard top-k, so a router near-tie that rounding flips changes a
    token's output by more than any logit margin.
    Also reported: the largest margin at which a mismatch occurred (what
    the margin is set from)."""
    n_pos = n_checked = n_bad = n_agree = 0
    worst = 0.0
    for prompt, generated in samples:
        prompt = np.asarray(prompt, np.int32)
        generated = np.asarray(generated, np.int32)
        ids = np.concatenate([prompt, generated])
        lg = reference.logits(ids)
        # the logits at position t predict token t + 1
        lg = lg[prompt.size - 1: ids.size - 1]
        lg = np.asarray(lg)
        top2 = np.partition(lg, -2, axis=-1)[:, -2:]      # [runner-up, best]
        arg = lg.argmax(axis=-1)
        gap = top2[:, 1] - top2[:, 0]
        same = arg == generated
        n_pos += generated.size
        n_agree += int(same.sum())
        judged = gap > margin
        n_checked += int(judged.sum())
        n_bad += int((judged & ~same).sum())
        if (~same).any():
            worst = max(worst, float(gap[~same].max()))
    share = n_checked / max(1, n_pos)
    agreement = n_agree / max(1, n_pos)
    return {"name": "tokens_vs_reference", "requests": len(samples),
            "positions": n_pos, "judged": n_checked, "mismatched": n_bad,
            "judged_share": share, "agreement": agreement,
            "worst_mismatch_margin": worst, "margin": float(margin),
            "min_agreement": float(min_agreement),
            "mismatch_share": n_bad / max(1, n_checked),
            "max_mismatch_share": float(max_mismatch_share),
            "ok": (n_pos > 0 and n_bad <= max_mismatch_share * n_checked
                   and share >= min_checked_share
                   and agreement >= min_agreement)}


def verdict(checks) -> bool:
    return bool(checks) and all(c["ok"] for c in checks)
