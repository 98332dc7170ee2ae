"""Forward passes a lane made for each block it committed, the commit
counted, over the window: ``engine.diffusion.lane_passes`` over
``engine.diffusion.blocks`` (``GET /stats``, after less before; the decode
programs' own counts, added up a pass at a time INSIDE the scans that run the
passes and read back with each dispatch's tokens: a pass left out is a pass
not counted). 5.0 when sound at four denoising steps; what fusing the commit
into the next block's first pass, fewer steps or a confident reveal lower. A
program without ``engine.diffusion`` gives nothing to read."""

from bench.period_stats import ratio


def read(facts, spec):
    return ratio(facts, "engine.diffusion.lane_passes", "engine.diffusion.blocks")
