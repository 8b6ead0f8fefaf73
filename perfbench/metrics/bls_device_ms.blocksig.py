"""bls_device_ms.blocksig: device milliseconds per flush: the union of
device operation intervals inside each flush's ``bench.flush`` annotation,
from the profiler trace, averaged over the traced flushes."""


def read(run):
    per = [busy for name, _, busy in (run.trace or {}).get("per_annotation", [])
           if name == "bench.flush"]
    return 1e3 * sum(per) / len(per) if per else None
