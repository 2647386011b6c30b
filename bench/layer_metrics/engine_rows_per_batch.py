"""Real (unpadded) query rows per device batch that the serving engine
dispatched in the window, from its own counters (`EngineStats`)."""


def read(rec):
    eng = rec["window"].get("engine")
    if not eng or not eng["batches"]:
        return None
    return eng["rows"] / eng["batches"]
