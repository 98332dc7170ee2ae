"""Own device milliseconds a whole decode chunk (``^jit_decode_chunk``) under
the part ``feed_forward`` in Qwen3-Next's cell: ``decode_feed_forward_dev_ms``'s
reader and specification, whole (that entry's ``workloads`` lists older cells
and is not a later PR's to edit; a ``benchmark`` PR lists this cell there and
drops this name). The cell's first reason: twelve layers' router, softmax and
choice of 10 in 512, the sort, three grouped products over the 128 experts
held at about a row a touched expert, and the gated shared expert (its own
scope, ``feed_forward/shared_expert``, inside this part). It stands beside
``qwen3next_decode_dispatch_dev_ms``, of which it is a part, and is the
device's own reading of what ``qwen3next_expert_stream_pct`` estimates from
the host's clock."""

from bench.layer_metrics.decode_feed_forward_dev_ms import read  # noqa: F401
