"""The benchmark's own yardstick: manifest loading, arithmetic on samples,
the reduction from a profiler trace to device metrics, and the device
table. Nothing here imports the program under test."""
