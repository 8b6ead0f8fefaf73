"""bls_msm_device_ms.blocksig: device milliseconds per traced flush of
the programs launched inside a ``bls.stage.msm`` span (the pubkey tree
sum, the G1 and G2 scaling and their normalisation), each launch matched
to its device module and credited to the spans open at its start
(``span_reduce``).  None when no launch is credited to that stage."""
import span_reduce

STAGE = "bls.stage.msm"


def read(run):
    r = span_reduce.reduce_run(run)
    if not r:
        return None
    s = sum(v for path, v in r["device_s"].items()
            if STAGE in path.split("/"))
    return 1e3 * s / r["annotations"] if s else None
