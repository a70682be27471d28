"""The yardstick: schedule, client timing, arithmetic, peaks and the
trace reduction. Nothing here imports the program under test."""
