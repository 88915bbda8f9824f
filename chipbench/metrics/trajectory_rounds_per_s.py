"""(scenario x seed) trajectory-rounds of the studies finished in the
window, over the window's host-clock seconds."""


def read(rec):
    rounds = sum(st.seeds * rec.config["steps"] for st, _ in rec.studies)
    return rounds / rec.window_s
