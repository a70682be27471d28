"""A check that records the moments it was called at and finds
something wrong at each: the test's stand-in for a comparison with a
reference."""

CALLS = []


def before_window(run):
    CALLS.append(("before_window", run.url, run.out, run.seed))
    return [f"told_twice before the window of {run.config['name']}"]


def after_exit(run):
    CALLS.append(("after_exit", run.url, run.out, run.seed))
    run.compared["told_twice_gap"] = [2.0, 1.0]
    return ["told_twice after the exit"]
