# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
import re

import jax


def default_interpret() -> bool:
    """Pallas interpret-mode default: compiled on TPU, interpreted
    everywhere else (CPU CI / this container). Kernel entry points take
    interpret=None and resolve it here at call time, so the same code
    path runs on both backends without flags."""
    return jax.default_backend() != "tpu"


def compiled_kernels(hlo_text: str) -> set:
    """Names of the Pallas kernels that a compiled program runs as TPU
    custom calls, read from `compiled.as_text()`. Each kernel passes its
    `name` to pallas_call, which tags the call's op_name
    ".../<name>/pallas_call"; an interpreted kernel leaves no custom
    call, so it is not counted."""
    found = set()
    for line in hlo_text.splitlines():
        if '"tpu_custom_call"' in line:
            m = re.search(r'op_name="[^"]*/(\w+)/pallas_call"', line)
            if m:
                found.add(m.group(1))
    return found


def resolve_kernel_flag(flag) -> bool:
    """Dispatch rule for the tri-state kernel perf levers on ModelConfig
    (ragged_decode_attn, fused_decode_altup):

      None  -> auto: the kernel runs where it compiles (TPU); interpret
               backends (CPU CI) take the dense jnp path, which is the
               kernels' allclose oracle anyway.
      True  -> force the kernel (interpret mode off-TPU — used by the
               oracle/serving tests to exercise the kernel path on CPU).
      False -> force the dense fallback everywhere.
    """
    if flag is None:
        return not default_interpret()
    return bool(flag)
