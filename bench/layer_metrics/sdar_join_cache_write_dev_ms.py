"""Own device milliseconds a whole join (``^jit_prefill_join``) under the part
``cache_write`` in SDAR's cell: ``join_cache_write_dev_ms``'s reader and
specification, whole (that entry's ``workloads`` is not a later PR's to
edit). The window's K and V rows into the page pool."""

from bench.layer_metrics.join_cache_write_dev_ms import read  # noqa: F401
