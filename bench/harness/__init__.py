"""The benchmark's harness: traffic generation, timing, trace reduction,
the plain references and the comparison that decides ``correct``.

Nothing here imports the program under test except the runners in
``bench/kinds``, which build and drive it."""
