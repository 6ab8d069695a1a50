"""Rows the prefill's expert form multiplied over the routed slots it
multiplied them for (a ``trinity`` cell's): what the grouped tiles' padding
costs."""

from cdtbench.kinds.trinity import moved


def read(ctx):
    if ctx["cell"].config.get("kind") != "trinity":
        return None
    rows = moved(ctx, "cdt_llm_expert_rows_total",
                 {"form": "^(grouped|dense)$"})
    slots = moved(ctx, "cdt_llm_expert_slots_total",
                  {"phase": "^prefill$", "where": "^held$"})
    if not rows or not slots:
        return None
    return rows / slots
