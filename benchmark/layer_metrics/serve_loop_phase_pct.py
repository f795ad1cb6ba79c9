"""The share of the traced window that the serving loop spent in one phase
of its turn: the summed duration of that phase's spans over the window.
``.admit``, ``.dispatch``, ``.sync`` and ``.bookkeep`` are the phases of
``SlotServer.step()``; ``.sync`` is the loop waiting for the device, with
``ServeApp.lock`` held; ``.app`` is what ``ServeApp`` adds around the step
under the same lock (``serve.loop.drain`` + ``.observe`` + ``.deliver``).
The phases are leaves, so the five shares sum to at most 100."""
import lib

PHASES = {
    "admit": ("serve.step.admit",),
    "dispatch": ("serve.step.dispatch",),
    "sync": ("serve.step.sync",),
    "bookkeep": ("serve.step.bookkeep",),
    "app": ("serve.loop.drain", "serve.loop.observe", "serve.loop.deliver"),
}


def read(facts, suffix):
    found = lib.load("trace/host_spans.py").spans("serve.")
    window = facts.get("trace_window_s")
    if not found or not window or suffix not in PHASES:
        return None
    spent = sum(dur for name, _, _, dur, _ in found if name in PHASES[suffix])
    return 100.0 * spent * 1e-9 / window
