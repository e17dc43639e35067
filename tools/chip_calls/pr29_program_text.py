"""PR 29: are the serving programs of the accepted cells the same text in
two trees?  ``pr27_program_text.py``'s report (sha256 of the lowered text
with every Mosaic kernel body replaced by the hash of its assembly without
debug information) for the Mistral-7B and the OLMoE serving programs
(``decode_step`` and two tiled ``put`` programs each), at the cells' real
sizes for a described v5e.  Nothing runs: no value, no time.

    JAX_PLATFORMS=cpu python3 tools/chip_calls/pr29_program_text.py <tree>
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import pr27_program_text as text        # noqa: E402  (chdir's into <tree>)

if __name__ == "__main__":
    print(f"tree {text.tree}")
    text.serve("mistral-7b-v0.1-serve-1chip")
    text.serve("olmoe-1b-7b-0125-serve-1chip")
