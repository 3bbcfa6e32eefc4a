"""Load imbalance of the held experts: for each expert layer, the most
token-assignments routed to one held expert in the window over the mean
per held expert, and the largest of those over the layers (1 is even).
Reads the engine's routed-assignment counter (``ServingEngine.
expert_load``), which the driver records over the window; nothing on a
program without it."""


def read(view):
    load = view.records.get("expert_load")
    if not load:
        return None
    ratios = [max(row) * len(row) / sum(row) for row in load if sum(row)]
    return max(ratios) if ratios else None
