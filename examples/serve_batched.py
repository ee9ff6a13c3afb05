"""Batched serving demo: the serving engine (scheduler + paged KV cache +
chunked prefill) over the three serving families — dense, hybrid (Jamba:
SSM layers give O(1)-state decode), and recurrent (xLSTM).

More requests than slots, so continuous batching refills finished slots from
the admission queue (EXPERIMENTS.md §Serving).

Run:  PYTHONPATH=src python examples/serve_batched.py
"""

from repro.launch import serve

if __name__ == "__main__":
    print("== dense (gemma3 family) ==")
    serve.main(["--arch", "gemma3-1b", "--requests", "8", "--slots", "4",
                "--prompt-len", "64", "--gen-len", "12"])
    print("== hybrid (jamba family: mamba + attention + MoE) ==")
    serve.main(["--arch", "jamba-1.5-large-398b", "--requests", "4",
                "--slots", "2", "--gen-len", "8"])
    print("== recurrent (xlstm family) ==")
    serve.main(["--arch", "xlstm-1.3b", "--requests", "4", "--slots", "2",
                "--gen-len", "8"])
