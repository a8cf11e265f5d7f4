import os
import sys

# allow `pytest tests/` without PYTHONPATH=src
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# The x64 bit-identity tests hold the batched CPU kernels to the serial
# reference one rounding per written operation.  XLA:CPU otherwise contracts
# multiply-add pairs into FMAs wherever its fusion emitters choose, which
# differs between differently shaped programs.  Capping the ISA at AVX (no
# FMA3) removes the contraction.  The flag must be set before JAX creates its
# CPU backend; subprocess tests inherit it.
os.environ["XLA_FLAGS"] = " ".join(
    f for f in (os.environ.get("XLA_FLAGS", ""), "--xla_cpu_max_isa=AVX") if f)
