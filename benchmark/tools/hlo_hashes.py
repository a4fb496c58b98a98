"""SHA-256 of the optimized HLO of the dense cells' programs, compiled for a
described v5e with no chip attached (``benchmark/offchip.py``): the train
step, ``_spec_round``, ``_spec_admit`` at 2048 and at 64.

    python benchmark/tools/hlo_hashes.py

Source locations are stripped (``metadata={...}`` and the tables of file
names and stack frames an HLO module ends with), and a Mosaic kernel's
serialized body is lowered with the innermost frame alone as its location
(the kernel's own file), so two checkouts whose programs are the same
operations hash alike though the callers' line numbers differ.  Run both
from one path: a kernel body still names its file.  A PR that must leave those programs alone runs this on its parent
and on itself and writes both into PERF.md.  One JSON line.
"""

import hashlib
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def stripped(text: str) -> str:
    text = re.sub(r",? ?metadata=\{[^{}]*\}", "", text)
    lines, in_table = [], False
    for line in text.splitlines():
        if line.strip() in TABLES:
            in_table = True
            continue
        if in_table and (re.match(r"^\d+ ", line) or not line.strip()):
            continue
        in_table = False
        lines.append(line)
    return "\n".join(lines)


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from benchmark import harness, offchip

    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    device = offchip.topology_device()
    train = harness.resolve_cell("gpt2m-train-1chip")
    serve = harness.resolve_cell("mistral7b-serve-offline")
    programs = {
        "train_step": lambda: offchip.compile_train_step(train, device),
        "spec_round": lambda: offchip.compile_spec_round(serve, device),
        "spec_admit_2048": lambda: offchip.compile_spec_admit(
            serve, device, 2048),
        "spec_admit_64": lambda: offchip.compile_spec_admit(serve, device, 64),
    }
    out = {}
    for name, build in programs.items():
        text = stripped(build().as_text())
        out[name] = hashlib.sha256(text.encode()).hexdigest()[:16]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
