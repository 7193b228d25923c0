"""Serve a small *language model* with batched requests (prefill + decode).

This drives the transformer scaffold's serving launcher
(repro.launch.serve) — KV-cache prefill plus a jit'd decode loop. The
paper's model (ODM) has its own serving subsystem with compiled
artifacts, Nyström compression and a microbatching scorer: see
``examples/serve_odm.py`` and ``repro.serve``.

    PYTHONPATH=src python examples/serve_lm.py --arch qwen3-0.6b
"""
import sys

from repro import compile_cache
from repro.launch.serve import main

if __name__ == "__main__":
    compile_cache.enable()
    sys.exit(main(sys.argv[1:] or
                  ["--arch", "qwen3-0.6b", "--batch", "4",
                   "--prompt-len", "32", "--gen", "16"]))
