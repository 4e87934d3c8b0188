"""From traces and shapes to numbers: peaks, needed work, busy and idle."""
