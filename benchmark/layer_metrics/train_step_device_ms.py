"""Mean device duration of one execution of the train-step program."""
import lib


def read(facts, suffix):
    names = facts.get("programs", {}).get("train_step")
    if not names:
        return None
    runs, seconds = lib.load("trace/reduce.py").program_time(
        facts["trace"], names)
    return seconds / runs * 1e3 if runs else None
