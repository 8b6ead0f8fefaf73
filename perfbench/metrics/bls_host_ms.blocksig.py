"""bls_host_ms.blocksig: host milliseconds per flush inside the RLC
combine that are not device stages: each ``bls.rlc.combine`` span less
its own ``bls.stage.msm``, ``bls.stage.htc`` and ``bls.stage.pairing``
child spans, which wait for the device in a traced run.  What is left is
scalar derivation, G2 signature decompression and subgroup checks,
pubkey row lookup, ``bls.stage.host_pack`` and ``bls.stage.hash_to_field``.
The per-set bisect's own stages, outside the combine, do not count."""

COMBINE = "bls.rlc.combine"
DEVICE_STAGES = ("bls.stage.msm", "bls.stage.htc", "bls.stage.pairing")


def read(run):
    per = []
    for op in run.ops:
        paths = [p for p in op.spans if p.split("/")[-1] == COMBINE]
        if paths:
            per.append(sum(op.spans[p] - sum(op.spans.get(p + "/" + s, 0.0)
                                             for s in DEVICE_STAGES)
                           for p in paths))
    return 1e3 * sum(per) / len(per) if per else None
