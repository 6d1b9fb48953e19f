"""Mean over 10 Hz samples of the scheduler's running sequences over the
decode lanes, all replicas together."""


def read(run):
    if not run.running_samples:
        return None
    lanes = run.lanes * len(run.pods)
    return 100.0 * sum(map(sum, run.running_samples)) / (
        lanes * len(run.running_samples)
    )
