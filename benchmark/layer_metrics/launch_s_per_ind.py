"""Host time the jitted train and eval calls of the window took to return
(``dispatch_s`` of the fenced device spans) per individual trained: launch,
apart from the wait for the device that follows in the same span."""
import spanlib


def read(run):
    found = [r for kind in ("train", "eval") for r in spanlib.device_spans(run, kind)
             if "dispatch_s" in r["attrs"]]
    n = spanlib.trained(run)
    return sum(r["attrs"]["dispatch_s"] for r in found) / n if found and n else None
