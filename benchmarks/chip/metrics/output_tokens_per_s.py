"""Tokens generated by the flows completed in the window, over the window
(which ends on the completion of a flow, so it holds whole flows only)."""

from benchmarks.chip.readers import flows_in_window


def read(run, cell):
    if not run.data.get("flows"):
        return None
    tokens = len(flows_in_window(run)) * run.data["batch"] * \
        run.data["new_tokens"]
    return tokens / run.window_s
