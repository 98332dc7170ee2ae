"""Own device milliseconds a whole decode dispatch (``^jit_decode_chunk``: two
blocks, ten passes) under the part ``feed_forward`` in SDAR's cell:
``decode_feed_forward_dev_ms``'s reader and specification, whole (that
entry's ``workloads`` lists older cells and is not a later PR's to edit; a
``benchmark`` PR lists this cell there and drops this name). The cell's first
reason: sixty times a dispatch the router, the softmax and the choice of 8 in
128, the sort, and three grouped products over ALL 128 experts of a layer at
about 15 rows an expert. It stands beside ``sdar_decode_dispatch_dev_ms``, of
which it is a part, and under ``sdar_expert_stream_pct``."""

from bench.layer_metrics.decode_feed_forward_dev_ms import read  # noqa: F401
