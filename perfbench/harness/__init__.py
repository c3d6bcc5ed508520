"""The chip benchmark's harness: traffic and table generation, the drive
loop, the plain reference, the trace reduction, peaks and counts. It
imports the program under test (``src/repro``) only to build and drive
it; the yardstick here imports nothing of it."""
