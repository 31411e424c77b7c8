"""spread-1m-pct5's control (door 5): the in-wave count is switched off, so
the counts as they stood when a wave began decide alone, as they did
before the program counted inside the wave.  The 256 pods of a Deployment
in a wave then all see the same counts and crowd the zones that stood at
the minimum; ``zone_skew_exceeded`` (references/spread.py) reads far above
0 and nothing else moves: every pod is still bound once, to an open node
with room."""


def plant(store, coord):
    coord.in_wave_skew = False
