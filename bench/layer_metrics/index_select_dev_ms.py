"""Own device milliseconds a whole decode chunk (``^jit_decode_chunk``) under
the scopes ``mixer/index_scores`` and ``mixer/index_select``
(``bench/sparse_scopes.py``): what choosing the tokens costs a dispatch,
beside ``decode_mixer_dev_ms`` of which it is a part."""

from bench.sparse_scopes import scope_seconds


def read(facts, spec):
    got = scope_seconds(facts, spec)
    if got is None:
        return None
    own = got["own_s"]
    return 1e3 * (own.get("index_scores", 0.0) + own.get("index_select", 0.0)) / got["runs"]
