"""Run one benchmark function in a child that sees ``n`` host devices.

The device count must be forced before JAX initializes, so the mesh
benches measure in a fresh process.  The child is pinned to the CPU
backend: it measures host-device meshes, and an accelerator already held
by the parent would make it fail or hang.  A child that fails ends the
run with a non-zero exit; there is no in-process fallback.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
_TAG = "CHILD_JSON "


def run_child(module: str, func: str, args: tuple, n_devices: int,
              timeout: int = 900):
    """Return the JSON result of ``module.func(*args)`` run in a child with
    ``n_devices`` forced host devices; exit non-zero if the child fails."""
    call = f"{module}.{func}{tuple(args)!r}"
    script = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {str(REPO / 'src')!r})
        sys.path.insert(0, {str(REPO)!r})
        from {module} import {func}
        print({_TAG!r} + json.dumps({func}(*{tuple(args)!r})))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
    try:
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        sys.exit(f"{call} child timed out after {timeout} s")
    for line in r.stdout.splitlines():
        if line.startswith(_TAG):
            return json.loads(line[len(_TAG):])
    sys.stderr.write(r.stdout + r.stderr)
    sys.exit(f"{call} child failed (exit {r.returncode})")
