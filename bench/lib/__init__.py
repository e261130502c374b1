"""The yardstick's own arithmetic: statistics, peaks, operation and byte counts,
trace reduction and pool accounting. Nothing here imports the program."""
