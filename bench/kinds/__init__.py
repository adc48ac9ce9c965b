"""General traffic generators, one per ``kind`` of traffic file.

Each module defines ``Cell(designs, traffic, seed, log)`` with
``setup(seconds)``, ``window(seconds, run)``, ``release()`` and
``check(control=False)``; ``bench.harness`` drives them in that order.
"""
