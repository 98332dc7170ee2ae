"""Own device time under a vocabulary of ``jax.named_scope`` names inside
whole runs of a program: ``bench/sparse_scopes.scope_seconds`` with the
vocabulary an argument (that file's is the learned index's three names, and
is not a later PR's to edit). ``bench/parts.labelled`` walks the trace file's
metadata and names each operation by the first name of the vocabulary on its
``tf_op`` path; the arithmetic is ``parts.part_seconds``'s: own time inside
the WHOLE runs of the programs ``module`` picks. A program without the scopes
(the parent of the PR that brought them), no trace file or no whole run gives
None.
"""

from __future__ import annotations

import bisect
import glob

from bench import parts, xplane


def scope_seconds(facts: dict, module: str, vocabulary: tuple[str, ...]) -> dict | None:
    """``{"runs", "own_s": {scope: seconds}}`` of the first chip."""
    files = sorted(glob.glob(f"{parts.TRACES}/plugins/profile/*/*.xplane.pb"))
    if not facts["trace"] or not files:
        return None
    planes = parts.labelled(files[0], vocabulary)
    chips = xplane.device_planes(planes)
    if not chips:
        return None
    ops, runs = xplane._whole_runs(planes[chips[0]], module)
    runs.sort(key=lambda r: r[1])
    starts = [a for _, a, _ in runs]
    own_s: dict = {}
    for label, a, b, own in xplane.own_events(ops):
        k = bisect.bisect_right(starts, a) - 1
        if label and k >= 0 and b <= runs[k][2]:
            own_s[label] = own_s.get(label, 0.0) + own
    return {"runs": len(runs), "own_s": own_s} if runs and own_s else None
