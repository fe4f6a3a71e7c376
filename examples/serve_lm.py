"""Serve a small model with batched requests admitted by the package-query
scheduler (the paper's technique as serving admission control).

    PYTHONPATH=src python examples/serve_lm.py
"""
from repro.launch.serve import main as serve_main


def main():
    serve_main(["--arch", "qwen2-1.5b-smoke", "--requests", "32",
                "--ticks", "8", "--max-batch", "8"])


if __name__ == "__main__":
    from repro.hostdev import use_compile_cache
    use_compile_cache()
    main()
