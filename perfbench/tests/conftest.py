"""The benchmark's own tests run on the host CPU: JAX on the CPU, and
the BLS limb kernels on their numpy mirror (``CS_TPU_NUMPY_KERNELS``),
which runs the program's staged pipeline eagerly in seconds where XLA:CPU
would compile for many minutes.  Both switches are read at import, so
they are set here, before any test module imports the program."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("CS_TPU_NUMPY_KERNELS", "1")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

tiny.setup_paths()
