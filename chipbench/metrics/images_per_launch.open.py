"""Images per launch: the window's ``NetStats`` coalesced images over
dispatches."""


def read(rec):
    d = rec["stats_delta"]
    return d["coalesced_images"] / d["dispatches"] if d["dispatches"] else None
