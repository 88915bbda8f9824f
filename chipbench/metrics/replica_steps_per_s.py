"""Local SGD steps that live replicas actually took (the payload's own
``trained`` count per round) in the studies finished in the window, over
the window's host-clock seconds."""


def read(rec):
    if not rec.config.get("payload"):
        return None
    steps = sum(int(out["trained"].sum()) for _, out in rec.studies)
    return steps / rec.window_s
