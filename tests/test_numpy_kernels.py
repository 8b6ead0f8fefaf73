"""The numpy mirror of the limb kernels (CS_TPU_NUMPY_KERNELS=1).

The same kernel source (``ops/jax_bls``) executes on numpy arrays with
python-shim control flow (``ops/jax_bls/backend.py``).  This mode backs
the multichip dryrun's hybrid fallback on hosts where XLA:CPU cannot
compile the staged pipeline inside the driver budget, so its
correctness IS a driver-facing guarantee.  The switch is import-time,
hence the subprocess.
"""
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHECK = r"""
import sys
sys.path.insert(0, %(repo)r)
import numpy as np
from consensus_specs_tpu.ops.jax_bls.backend import NUMPY_KERNELS
assert NUMPY_KERNELS
import jax  # tree_util only

from consensus_specs_tpu.utils import bls
from consensus_specs_tpu.ops import bls_jax
from consensus_specs_tpu.ops.jax_bls import htc as HTC, points as PT
from consensus_specs_tpu.ops.bls12_381 import hash_to_curve as ORC

bls.use_py()
wide = %(wide)r
# hash-to-curve equals the pure-python oracle
msgs = [b"np-kernel-0", b"np-kernel-1"] if wide else [b"np-kernel-0"]
pts = HTC.hash_to_g2_batch(msgs)
for i, m in enumerate(msgs):
    got = PT.g2_unpack(jax.tree_util.tree_map(lambda a: a[i], pts))
    assert got == ORC.hash_to_g2(m), "htc mismatch"

# a real aggregate verifies; a wrong message does not
sks = [1, 2, 3, 4] if wide else [1, 2]
msg = b"np-kernel-agg"
pks = [bls.SkToPk(sk) for sk in sks]
agg = bls.Aggregate([bls.Sign(sk, msg) for sk in sks])
items = [(pks, msg, agg), (pks, msg + b"!", agg)]
out = bls_jax.verify_aggregates_batch(items)
assert out == [True, False], out
print("NUMPY-KERNELS-OK")
"""


def _run_check(wide: bool):
    env = dict(os.environ, CS_TPU_NUMPY_KERNELS="1",
               CS_TPU_BLS_BATCH="2")
    proc = subprocess.run(
        [sys.executable, "-c", _CHECK % {"repo": _REPO, "wide": wide}],
        env=env, capture_output=True, timeout=300, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    assert b"NUMPY-KERNELS-OK" in proc.stdout


def test_numpy_kernel_mirror_matches_oracle():
    _run_check(wide=False)


import pytest  # noqa: E402
from consensus_specs_tpu.utils.env_flags import HEAVY  # noqa: E402


@pytest.mark.skipif(not HEAVY, reason="wider numpy-mirror differential "
                    "(CS_TPU_HEAVY=1)")
def test_numpy_kernel_mirror_wide():
    _run_check(wide=True)


_FR_FFT_CHECK = r"""
import sys
sys.path.insert(0, %(repo)r)
from consensus_specs_tpu.ops.jax_bls.backend import NUMPY_KERNELS
assert NUMPY_KERNELS
import random
from consensus_specs_tpu.ops import kzg as K
from consensus_specs_tpu.ops import kzg_7594 as K7
from consensus_specs_tpu.ops.jax_bls import fr_fft

rng = random.Random(61)
n = 256
roots = list(K.compute_roots_of_unity(n))
rows = [[rng.randrange(K.BLS_MODULUS) for _ in range(n)] for _ in range(4)]
assert fr_fft.fft_batch(rows, roots) == \
    [K7.fft_field(r, roots) for r in rows]
assert fr_fft.fft_batch(rows, roots, inv=True) == \
    [K7.fft_field(r, roots, inv=True) for r in rows]
# round trip through the kernel alone
back = fr_fft.fft_batch(fr_fft.fft_batch(rows, roots), roots, inv=True)
assert back == rows

# the DAS recovery grouped phases under CS_TPU_DAS_FFT=limb are
# byte-identical to the host-int path
import os
from consensus_specs_tpu.das import kernels
setup = K.trusted_setup("minimal")
blob = b"".join(rng.randrange(K.BLS_MODULUS).to_bytes(32, "big")
                for _ in range(setup.FIELD_ELEMENTS_PER_BLOB))
cells = K7.compute_cells(blob, setup)
n_cells = K7.cells_per_blob(setup)
keep = sorted(rng.sample(range(n_cells), n_cells // 2))
def _bytes(c):
    return b"".join(int(x).to_bytes(32, "big") for x in c)
reqs = [(keep, [_bytes(cells[i]) for i in keep])]
host = kernels.recover_cells_batch(reqs, setup)
os.environ["CS_TPU_DAS_FFT"] = "limb"
limb = kernels.recover_cells_batch(reqs, setup)
assert host == limb
print("FR-FFT-NUMPY-OK")
"""


def test_fr_fft_numpy_mirror_matches_host_fft():
    """The Fr limb FFT (DAS recovery kernel) in numpy-mirror mode:
    byte-identical to the python-int FFT, forward/inverse/roundtrip,
    and the full recovery pipeline under CS_TPU_DAS_FFT=limb."""
    env = dict(os.environ, CS_TPU_NUMPY_KERNELS="1")
    proc = subprocess.run(
        [sys.executable, "-c", _FR_FFT_CHECK % {"repo": _REPO}],
        env=env, capture_output=True, timeout=300, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    assert b"FR-FFT-NUMPY-OK" in proc.stdout
