"""Serving scenario: continuous batching with RowClone-backed paged KV.

Eight requests; the second four share the first request's prompt prefix
(think: same system prompt).  Prefix pages are shared (refcounted), the
divergent tails are copy-on-write RowClone page copies, freed pages are
zeroed in-memory (pim_init).

Run:  PYTHONPATH=src python examples/serve_rowclone.py   (toy widths, CPU)
"""

import sys

sys.argv = [sys.argv[0], "--arch", "granite-3-8b", "--reduced",
            "--requests", "8", "--prompt-len", "24", "--max-new", "8",
            "--share-prefix", "--page-size", "8"]

from repro.launch.serve import main

if __name__ == "__main__":
    main()
