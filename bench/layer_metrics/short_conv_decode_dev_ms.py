"""Own device milliseconds a whole decode chunk (``^jit_decode_chunk``) under
the scope ``mixer/short_conv`` (``cake_tpu/ops/short_conv.py``: the gated
short convolution's three multiply-adds a channel and its output gate, of
every ``conv`` layer and step of the chunk): what the convolutions themselves
cost a dispatch, beside ``decode_mixer_dev_ms`` of which it is a part. Their
projections sit under ``mixer_in`` / ``mixer_out`` and the window's write
under ``cache_write``. A few percent of a chunk or less is the compiler
fusing it between the two products; more asks for a kernel."""

from bench.scope_times import scope_seconds

SCOPES = ("short_conv",)


def read(facts, spec):
    got = scope_seconds(facts, spec["pattern"]["module"], SCOPES)
    if got is None:
        return None
    return 1e3 * got["own_s"].get("short_conv", 0.0) / got["runs"]
