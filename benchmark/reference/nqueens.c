/* Plain reference for the N-Queens cells: a recursive backtracking count,
 * written from the problem's definition and sharing no code with the
 * program under test.
 *
 *   nqueens --N N [--control 1]
 *
 * prints {"tree": T, "sol": S}.
 *
 * Queens go column by column; a child puts the next queen on a row that no
 * queen holds and no diagonal attacks.  Every child is counted in `tree`,
 * the complete boards among them too, and each complete board once more in
 * `sol` (the conventions of the Chapel reference the program follows).
 *
 * `--control 1` is the control: rows are stored in 4 bits
 * and the diagonal test `row == other +- distance` is computed modulo 16,
 * so that a run of it in the program's place must come out as not correct.
 */
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

static int N;
static long long tree, sol;

/* Sound count: one bit per row and per diagonal. */
static void count(int col, unsigned rows, unsigned up, unsigned down) {
    unsigned full = (1u << N) - 1;
    unsigned free_rows = full & ~(rows | up | down);
    while (free_rows) {
        unsigned bit = free_rows & -free_rows;
        free_rows ^= bit;
        tree++;
        if (col + 1 == N)
            sol++;
        else
            count(col + 1, rows | bit, ((up | bit) << 1) & full, (down | bit) >> 1);
    }
}

/* Control: the same search with 4-bit rows. */
static int board[32];

static int safe4(int col, int row) {
    for (int i = 0; i < col; i++) {
        int d = col - i;
        if (((board[i] - row + d) & 15) == 0 || ((board[i] - row - d) & 15) == 0)
            return 0;
    }
    return 1;
}

static void count4(int col, unsigned rows) {
    for (int row = 0; row < N; row++) {
        if ((rows >> row & 1) || !safe4(col, row))
            continue;
        tree++;
        board[col] = row & 15;
        if (col + 1 == N)
            sol++;
        else
            count4(col + 1, rows | 1u << row);
    }
}

int main(int argc, char **argv) {
    int int4 = 0, bad = argc % 2 != 1;
    N = 0;
    for (int a = 1; a + 1 < argc; a += 2) {
        if (!strcmp(argv[a], "--N"))
            N = atoi(argv[a + 1]);
        else if (!strcmp(argv[a], "--control"))
            int4 = atoi(argv[a + 1]);
        else
            bad = 1;
    }
    if (bad || N < 1 || N > 30) {
        fprintf(stderr, "usage: %s --N N [--control 1]\n", argv[0]);
        return 2;
    }
    if (int4)
        count4(0, 0);
    else
        count(0, 0, 0, 0);
    printf("{\"tree\": %lld, \"sol\": %lld}\n", tree, sol);
    return 0;
}
