"""client planning: host milliseconds of ``BBClient.encode`` (path hashing
and request arrays) per create, stat and remove call, from the benchmark's
``bench.encode`` spans (moves ``md_kops``)."""
from layer_common import META_OPS


def read(run):
    t = run.trace
    calls = t.calls(META_OPS)
    enc = t.spans_named("bench.encode", calls)
    if not calls or not enc:
        return None
    return sum(e - s for s, e in enc) / 1e6 / len(calls)
