"""``decode_state_stream_pct``'s quantity in a cell whose state layers run
the gated delta rule (that entry's ``workloads`` lists Jamba's cell alone and
is not a later PR's to edit; this file reads the same counters): the share of
a decode step's device time that streaming the recurrent state alone accounts
for. Every live lane's state is read and written once a step (``2 x
lanes_live_mean x engine.state.bytes_per_lane``, both from ``GET /stats``
over the window), over the peak HBM bandwidth, over the measured device time
of one step (a ``jit_decode_chunk_*`` program's mean time divided by the
tokens it makes, ``--decode-chunk``). A FLOOR over the live lanes: the
program steps every row of the dispatch, live or not (the update's kernel
walks them all), so at 16 of 32 lanes live it moves about twice this. A
program without state layers (or from before ``engine.state``) gives nothing
to read and the metric is left out."""

from statistics import fmean

from bench.costs import peaks
from bench.period_stats import PERIOD, dig, ratio


def read(facts, spec):
    runs = (facts["trace"] or {}).get("programs", {}).get(facts["metric"])
    per_lane = dig(facts["stats_after"], "engine.state.bytes_per_lane")
    lanes = ratio(facts, f"{PERIOD}.lane_seconds.live", f"{PERIOD}.seconds")
    if not runs or not per_lane or lanes is None:
        return None
    flags = facts["config"]["server_flags"]
    step_s = fmean(runs) / int(flags[flags.index("--decode-chunk") + 1])
    moved = 2.0 * lanes * per_lane
    floor_s = moved / (peaks(facts["device"]["device_kind"])["hbm_gb_per_s"] * 1e9)
    return 100.0 * floor_s / step_s
