"""What the program wrote on its spans of one name (``params["span"]``),
over the spans of the window: with ``equals``, the share of them, in
percent, whose ``attribute`` has that value; else the ``q``-th percentile of
the attribute over the spans that carry it. None where no span of the name
carries the attribute: a program that does not write it."""

from benchmarks.harness.stats import percentile


def read(obs, params):
    spans = [s for s in obs.get("spans", []) if s["name"] == params["span"]]
    values = [s["attributes"][params["attribute"]] for s in spans
              if params["attribute"] in s["attributes"]]
    if not values:
        return None
    if "equals" in params:
        return 100.0 * sum(v == params["equals"] for v in values) / len(spans)
    return percentile(values, params["q"])
