"""Share of peak HBM bandwidth that streaming the weights alone accounts
for in a decode step: bytes of weights a chip must read for one token
(``costs.decode_weight_bytes``: weights only, the KV cache's bytes are not
counted) over the peak, over the measured device time of one step (a decode
program's time divided by the tokens it makes, ``--decode-chunk``)."""

from statistics import fmean

from bench.costs import decode_weight_bytes, peaks


def read(facts, spec):
    runs = (facts["trace"] or {}).get("programs", {}).get(facts["metric"])
    if not runs:
        return None
    cfg = facts["config"]
    flags = cfg["server_flags"]
    chunk = int(flags[flags.index("--decode-chunk") + 1])
    step_s = fmean(runs) / chunk
    moved = decode_weight_bytes(cfg, cfg["served_dtype"])
    floor_s = moved / (peaks(facts["device"]["device_kind"])["hbm_gb_per_s"] * 1e9)
    return 100.0 * floor_s / step_s
