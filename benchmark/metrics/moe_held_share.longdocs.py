"""Scheduler: of the token -> expert assignments the routed layers made
in the window (tokens x ``num_experts_per_tok`` x routed layers), the
share that landed on experts this chip HOLDS (the step record's
``moe_rows``, counted by the step itself), in percent: 25 where routing
is even over a quarter of the experts."""
from harness import program_spans


def read(run):
    records = program_spans.launched(run)
    if not records or any("moe_rows" not in r for r in records):
        return None
    cfg = run.config
    made = (sum(r["tokens"] for r in records) * cfg["num_experts_per_tok"]
            * (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]))
    return 100.0 * sum(r["moe_rows"] for r in records) / made
