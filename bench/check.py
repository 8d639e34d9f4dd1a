"""The comparison that decides `correct`.

Every number compared has a limit; a run is correct when each number is
at or under its limit.  The numbers, per operation of the traffic:

Where a mix cycles through several fields (snapshots), each number is
the worst over them, and each count their sum.

compress (the containers the window wrote, read back on the host by the
plain reference decoder the configuration names,
`bench.reference.<name>`):
  eb_gap          |eb in the header - eb_rel * (max - min)| / that eb
                  (the pipeline's error-bound resolution; exact, 0)
  err_over_bound  max |field - reference decode| over the configuration's
                  stated pointwise bound (limit 1, the guarantee itself)
  format_faults   inconsistencies the reference decoder met: checksum,
                  codebook (Kraft), word counts, gap arrays that do not
                  chain, codewords not in the book, outliers that do not
                  sit where the codes say (exact, 0)
  repeat_mismatch window containers whose bytes differ from the one
                  decoded (compressing one field is deterministic; 0)

decompress (the fields the window reconstructed on the device):
  err_over_bound  max |field - program's reconstruction| over the bound
  recon_mismatch  elements where the program's reconstruction differs
                  from the reference decode of the same container (the
                  decode is integer prefix sums and one float32 multiply
                  per value: exact, 0)
  format_faults   as above, for the container the window decoded
"""
from __future__ import annotations

import hashlib
import importlib
from typing import Dict, List, Sequence

import numpy as np

LIMITS = {"eb_gap": 0.0, "err_over_bound": 1.0, "format_faults": 0,
          "repeat_mismatch": 0, "recon_mismatch": 0}
COUNTS = ("format_faults", "repeat_mismatch")


def stated_bound(x: np.ndarray, eb: float) -> float:
    """The configuration's pointwise guarantee |d - d'| <= this: eb, up
    to float32 representability (the PREQUANT divide and the dequantising
    multiply each round once)."""
    eps = float(np.finfo(np.float32).eps)
    amax = float(np.max(np.abs(x)))
    return eb * (1.0 + 1e-5) + 4.0 * eps * amax + float(
        np.finfo(np.float32).tiny)


def max_abs_err(x: np.ndarray, y: np.ndarray, block: int = 1 << 24) -> float:
    """max |x - y| in float64, in blocks so that a 1 GB field fits."""
    xf, yf = x.reshape(-1), y.reshape(-1)
    worst = 0.0
    for i in range(0, xf.shape[0], block):
        d = np.abs(xf[i:i + block].astype(np.float64)
                   - yf[i:i + block].astype(np.float64))
        worst = max(worst, float(d.max()) if d.size else 0.0)
    return worst


def digest(header: dict, arrays: Dict[str, np.ndarray]) -> str:
    h = hashlib.sha256(repr(sorted(header["params"].items())).encode())
    for k in sorted(arrays):
        a = np.ascontiguousarray(arrays[k])
        h.update(f"{k}:{a.dtype.str}:{a.shape};".encode())
        h.update(a.data)
    return h.hexdigest()


def reference(name: str):
    """The plain reference a configuration names: bench/reference/<name>.py"""
    return importlib.import_module(f"bench.reference.{name}")


def check_container(x: np.ndarray, header: dict, arrays, eb_rel: float,
                    ref) -> Dict[str, float]:
    """eb_gap, err_over_bound and format_faults of one packed container
    against the field it was made from.  Returns the numbers and the
    reference's reconstruction (None where it could not decode)."""
    eb = float(header["params"]["eb"])
    eb_ref = float(eb_rel) * (float(x.max()) - float(x.min()))
    y, faults = ref.decode(header, arrays)
    nums = {"eb_gap": abs(eb - eb_ref) / eb_ref,
            "err_over_bound": (float("inf") if y is None else
                               max_abs_err(x, y) / stated_bound(x, eb_ref)),
            "format_faults": int(sum(faults.values()))}
    return nums, y, faults


def _merge(per_snapshot: List[Dict[str, float]]) -> Dict[str, float]:
    """The numbers of several snapshots as one: the worst of each, and
    the sum of the counts."""
    out: Dict[str, float] = {}
    for nums in per_snapshot:
        for k, v in nums.items():
            out[k] = (out.get(k, 0) + v if k in COUNTS
                      else max(out.get(k, v), v))
    return out


def check_compress(xs: Sequence[np.ndarray], outputs: Sequence,
                   eb_rel: float, ref: str, picks: Dict[int, int]):
    """`outputs`: (snapshot, (header, arrays)) of every window call;
    `xs`: the fields, by snapshot.  For each snapshot the reference
    decodes the output `picks[snapshot]` and every other output of that
    snapshot is compared with it byte for byte.  Returns (numbers,
    reference faults, outputs that failed)."""
    refmod = reference(ref)
    per, faults, failed = [], {}, 0
    for s, j in sorted(picks.items()):
        header, arrays = outputs[j][1]
        nums, _, f = check_container(xs[s], header, arrays, eb_rel, refmod)
        want = digest(header, arrays)
        mine = [out for t, out in outputs if t == s]
        nums["repeat_mismatch"] = sum(digest(h, a) != want for h, a in mine)
        sound = all(v["ok"] for k, v in verdict(nums).items()
                    if k != "repeat_mismatch")
        failed += nums["repeat_mismatch"] if sound else len(mine)
        per.append(nums)
        for k, v in f.items():
            faults[k] = faults.get(k, 0) + v
    return _merge(per), faults, failed


def check_decompress(xs: Sequence[np.ndarray], packed: Sequence,
                     outputs: Sequence, eb_rel: float, ref: str):
    """`outputs`: (snapshot, host copy of the field the window
    reconstructed from `packed[snapshot]`); `xs`: the fields, by
    snapshot.  Returns (numbers, reference faults, outputs that
    failed)."""
    refmod = reference(ref)
    per, faults, failed = [], {}, 0
    for s in sorted({t for t, _ in outputs}) or [0]:
        x = xs[s]
        nums, y_ref, f = check_container(x, *packed[s], eb_rel, refmod)
        bound = stated_bound(x, float(eb_rel) * (float(x.max())
                                                  - float(x.min())))
        ys = [y for t, y in outputs if t == s]
        errs = [max_abs_err(x, y) / bound for y in ys]
        miss = [x.size if y_ref is None or y.shape != y_ref.shape
                else int(np.count_nonzero(y.reshape(-1)
                                          != y_ref.reshape(-1)))
                for y in ys]
        nums["err_over_bound"] = max(errs or [float("inf")])
        nums["recon_mismatch"] = max(miss or [x.size])
        bad = sum(e > LIMITS["err_over_bound"] or m > 0
                  for e, m in zip(errs, miss))
        failed += len(ys) if nums["format_faults"] or nums["eb_gap"] else bad
        per.append(nums)
        for k, v in f.items():
            faults[k] = faults.get(k, 0) + v
    return _merge(per), faults, failed


def verdict(nums: Dict[str, float]) -> Dict[str, dict]:
    """Each number beside its limit, and whether it passed."""
    return {k: {"value": v, "limit": LIMITS[k],
                "ok": bool(np.isfinite(v) and v <= LIMITS[k])}
            for k, v in nums.items()}
