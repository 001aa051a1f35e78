"""Engine partitions: compiled partition programs dispatched per frame,
counted by the program's ``CompiledPlanCache`` (each hit or miss is one
lookup of a program that is then called) over the window."""


def read(run):
    if not run.frames:
        return None
    return run.programs / run.frames
