"""Tools for whoever defines a cell: readings for limits, the rate sweep."""
