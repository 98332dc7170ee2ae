"""Own device milliseconds a whole decode chunk (``^jit_decode_chunk``) under
the part ``feed_forward`` in LFM2's cell: ``decode_feed_forward_dev_ms``'s
reader and specification, whole (that entry's ``workloads`` lists older cells
and is not a later PR's to edit; a ``benchmark`` PR lists this cell there and
drops this name). Here it is the cell's reason: the router, the sort and the
three grouped products of 14 sparse layers, whose stream of every touched
expert is most of a step, and two dense feed-forwards. It stands beside
``lfm2_decode_dispatch_dev_ms``, of which it is a part, and is the device's
own reading of what ``lfm2_expert_stream_pct`` estimates from the host's
clock."""

from bench.layer_metrics.decode_feed_forward_dev_ms import read  # noqa: F401
