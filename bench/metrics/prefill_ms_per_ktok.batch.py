"""Prefill and admission (bucketed prefill: the ``prefill``, ``quant`` and
``scatter`` programs, which compute a prompt, quantize its latent cache to
fp8 pages and write them into the pool): their device time in the traced
window per 1,000 prompt tokens of the requests whose first token came
inside it (bucket padding is not counted as work)."""
from bench.metrics_lib import program_ms, program_runs

PROGRAMS = ("prefill", "quant", "scatter")


def read(rec):
    tokens = rec.get("traced_prompt_tokens")
    if not tokens or not program_runs(rec, "prefill"):
        return None
    return sum(program_ms(rec, p) for p in PROGRAMS) / (tokens / 1e3)
