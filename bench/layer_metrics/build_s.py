"""build_s: host-clock seconds of the device graph build
(``graph/dist_build.py``), which is most of set-up when it compiles."""


def read(run, trace):
    return run["build_s"]
