"""Mean device duration of one decode-block program, from the trace."""
import lib


def read(facts, suffix):
    names = facts.get("programs", {}).get("decode")
    if not names:
        return None
    runs, seconds = lib.load("trace/reduce.py").program_time(
        facts["trace"], names)
    return seconds / runs * 1e3 if runs else None
