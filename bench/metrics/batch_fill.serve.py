"""How full the engine's micro-batches were over the window: fields
served over batches run times ``max_batch``, from the engine's own
counters (``OperatorEngine.stats()["avg_batch_fill"]``)."""


def read(run):
    if run.kind != "serve":
        return None
    st = run.window["engine"]
    if not st.get("batches"):
        return None
    return st["fields_served"] / (st["batches"] * st["max_batch"]) * 100.0
