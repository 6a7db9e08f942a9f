"""Serving example: batched prefill + greedy decode of an FL-trained model.

  PYTHONPATH=src python examples/serve_lm.py --arch minicpm3-4b
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.launch import serve
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    serve.main()


if __name__ == "__main__":
    main()
