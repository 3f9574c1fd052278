"""Serving example: prefill + batched greedy decode on every arch family.

  PYTHONPATH=src python examples/serve_lm.py [--arch recurrentgemma-2b]

Decode-as-delta in action: recurrent archs (xlstm, recurrentgemma) carry
O(1) state per step; attention archs append to their KV cache (ring
buffer under sliding windows).
"""
import sys

from repro.compile_cache import enable_compile_cache
from repro.launch import serve

if __name__ == "__main__":
    sys.argv = [sys.argv[0], "--arch", "xlstm-350m", "--reduced",
                "--batch", "4", "--prompt-len", "16",
                "--new-tokens", "24"] + sys.argv[1:]
    enable_compile_cache()
    serve.main()
