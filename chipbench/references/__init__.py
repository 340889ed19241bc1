"""One module per plain reference, found by the name in a
configuration's ``reference``; each has ``solve(dcop, cycles, seed)
-> (assignment, cost)``, the cost in the problem's own sign."""
