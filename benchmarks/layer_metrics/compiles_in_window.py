"""Compile requests inside the measured window (`runtime.compile_stats`
delta, persistent-cache loads included).  Must be 0: every shape is warmed
in set-up."""


def read(obs):
    return obs.counters.get("compiles_in_window")
