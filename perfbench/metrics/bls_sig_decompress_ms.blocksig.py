"""bls_sig_decompress_ms.blocksig: host milliseconds per window flush of
the ``bls.stage.sig_decompress`` spans inside ``bls.rlc.combine``: G2
signature decompression and subgroup checks, before any device work.
The per-set bisect's own decompression, outside the combine, does not
count."""

COMBINE = "bls.rlc.combine"
STAGE = "bls.stage.sig_decompress"


def read(run):
    per, seen = [], False
    for op in run.ops:
        names = [p.split("/") for p in op.spans]
        if not any(n[-1] == COMBINE for n in names):
            continue
        stage = [p for p, n in zip(op.spans, names)
                 if n[-1] == STAGE and COMBINE in n[:-1]]
        seen = seen or bool(stage)
        per.append(sum(op.spans[p] for p in stage))
    return 1e3 * sum(per) / len(per) if seen else None
