"""Model step: model FLOPs of the work delivered in the traced window
(``bench/costs.py``: every prompt computed, every token decoded, each at
its own context) over the engine's busy wall time (the summed ``step()``
durations, each ending on its host sync) times chips times the chip's
bf16 peak (``bench/peaks.json``), in percent."""


def read(rec):
    busy = rec.get("busy_wall_s")
    if not busy or not rec.get("flops"):
        return None
    return 100.0 * rec["flops"] / (busy * rec["chips"] * rec["peak_flops"])
