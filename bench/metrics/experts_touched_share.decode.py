"""The mean share of the experts that got at least one row, over layers and
decode steps: each decode step of a MoE model leaves one flight-recorder
event, ``moe.expert_rows``, whose ``rows`` argument holds each layer's row
count per expert. A lower share means fewer experts' weights read a step.
None where no step left such an event."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "MoE layer", "%", "lower", "program_counter", "itl_p95_ms"
EVENT = "moe.expert_rows"


def read(ctx):
    shares = [sum(1 for r in row if r > 0) / len(row)
              for s in ctx["spans"] if s.name == EVENT
              for row in s.args["rows"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
